"""Warm-up, the measured window, and the arithmetic over its rounds.

The window holds nothing but the rounds: each iteration reads the host clock,
calls the system, and reads the clock again. Rounds come from a pool drawn
before the window opens. ``gc.collect()`` runs once just before it opens;
the collector stays on.
"""
from __future__ import annotations

import dataclasses
import gc
import math
import time
from typing import Callable, Sequence

import numpy as np

#: ``jax.monitoring`` events that mean a program was traced, lowered,
#: compiled or loaded from the persistent cache
COMPILE_DURATIONS = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
)
COMPILE_EVENTS = (
    "/jax/compilation_cache/cache_hits",
    "/jax/compilation_cache/cache_misses",
)


class CompileCounter:
    """Counts compile and cache-load events, and sums their seconds."""

    def __init__(self):
        self.count = 0
        self.seconds = 0.0

    def on_event(self, event: str, **_) -> None:
        if event in COMPILE_EVENTS:
            self.count += 1

    def on_duration(self, event: str, duration: float, **_) -> None:
        if event in COMPILE_DURATIONS:
            self.count += 1
            self.seconds += float(duration)

    def install(self) -> "CompileCounter":
        import jax

        jax.monitoring.register_event_listener(self.on_event)
        jax.monitoring.register_event_duration_secs_listener(self.on_duration)
        return self


@dataclasses.dataclass
class Window:
    """What the window saw: one entry per completed round."""

    round_s: list[float]  # each round's call, host clock
    end_s: list[float]  # each round's end, from the window's start
    decisions: list[int]  # decisions each round returned
    results: list  # the system's results, read after the window
    compiles: int  # compile / cache-load events inside the window

    @property
    def rounds(self) -> int:
        return len(self.round_s)

    def decisions_per_s(self) -> float:
        """Decisions of every round, over window start to the last round's end."""
        return float(sum(self.decisions)) / self.end_s[-1]

    def round_p95_ms(self) -> float:
        """95th percentile (linear interpolation) of every round's time."""
        return 1e3 * float(np.percentile(np.asarray(self.round_s), 95.0))


def warm_up(run: Callable, rounds: Sequence, counter: CompileCounter,
            log: Callable[[str], None], clean_needed: int = 2) -> tuple[list, list[float]]:
    """Run rounds until ``clean_needed`` in a row complete with no compile or
    cache-load event.

    Returns the results of every warm-up round (the reference replays them:
    they moved the scheduler's state) and the clean rounds' times.
    """
    results, clean = [], []
    for i, args in enumerate(rounds):
        before = counter.count
        t0 = time.perf_counter()
        results.append(run(args))
        dt = time.perf_counter() - t0
        events = counter.count - before
        log(f"warm-up round {i}: {dt:.4f} s, {events} compile/cache events")
        clean = clean + [dt] if events == 0 else []
        if len(clean) == clean_needed:
            return results, clean
    raise RuntimeError(f"{len(results)} warm-up rounds never ran {clean_needed} "
                       f"in a row without compiling; the window would compile")


def pool_size(seconds: float, round_s: float, slack: float = 4.0) -> int:
    """Rounds enough to fill ``seconds`` at ``slack`` times the warm rate."""
    return int(math.ceil(slack * seconds / max(round_s, 1e-6))) + 8


def measure(run: Callable, pool: Sequence, seconds: float, counter: CompileCounter,
            decisions: Callable[[object], int],
            before_round: Callable[[int, float], None] | None = None) -> Window:
    """The window: rounds from ``pool`` until ``seconds`` have passed.

    The window ends on a round boundary: the last round is the first to end
    at or after ``seconds``. ``before_round(i, elapsed)`` (traced runs only)
    may start or stop the profiler between rounds.
    """
    round_s, end_s, results = [], [], []
    gc.collect()
    c0 = counter.count
    t_open = time.perf_counter()
    for i, args in enumerate(pool):
        if before_round is not None:
            before_round(i, time.perf_counter() - t_open)
        t0 = time.perf_counter()
        res = run(args)
        t1 = time.perf_counter()
        results.append(res)
        round_s.append(t1 - t0)
        end_s.append(t1 - t_open)
        if t1 - t_open >= seconds:
            break
    compiles = counter.count - c0
    if end_s[-1] < seconds:
        raise RuntimeError(f"the pool of {len(pool)} rounds ran out after "
                           f"{end_s[-1]:.3f} s of a {seconds} s window")
    return Window(round_s, end_s, [decisions(r) for r in results], results,
                  compiles)
