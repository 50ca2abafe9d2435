"""The traced run: program spans over the whole window, the profiler over a
short slice near its end, and the reduction from trace to metrics.

Every round of a traced run is wrapped in a ``bench.round`` annotation, so
the profiler's trace marks where each round begins and ends on the same
clock as the device's operations and the program's ``closed_loop.*`` spans.
The reduction works on plain tuples, so a test can feed it a small trace.
"""
from __future__ import annotations

import dataclasses
import glob
import shutil
from collections import defaultdict
from pathlib import Path

ROUND_SPAN = "bench.round"
#: host spans a gap can be attributed to: the program's phases of a round
#: and the sub-spans that tile pack and dispatch (the innermost one wins)
PROGRAM_SPANS = ("closed_loop.pack", "closed_loop.dispatch", "closed_loop.epilogue",
                 "closed_loop.pack.arrivals", "closed_loop.pack.tables",
                 "closed_loop.pack.state", "closed_loop.dispatch.call",
                 "closed_loop.dispatch.wait", "closed_loop.dispatch.fetch")


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start: float  # seconds on the trace's clock
    end: float


@dataclasses.dataclass
class TraceData:
    devices: dict[str, list[Event]]  # device plane -> its operations
    host: list[Event]  # host annotations (rounds and program spans)
    lines: dict[str, list[str]]  # plane -> line names, for the record


def union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    """Merge overlapping intervals; the result is sorted and disjoint."""
    out: list[list[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def clip(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals if b > lo and a < hi]


def covered(intervals, lo: float, hi: float) -> float:
    return sum(b - a for a, b in clip(intervals, lo, hi))


def gaps(busy: list[tuple[float, float]], lo: float, hi: float) -> list[tuple[float, float]]:
    """The idle intervals of [lo, hi] between merged busy intervals."""
    out, t = [], lo
    for a, b in clip(busy, lo, hi):
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if hi > t:
        out.append((t, hi))
    return out


def attribute(gap: tuple[float, float], host: list[Event]) -> str:
    """The innermost host span covering the gap's midpoint."""
    mid = 0.5 * (gap[0] + gap[1])
    best = None
    for ev in host:
        if ev.start <= mid <= ev.end and (best is None or ev.end - ev.start < best.end - best.start):
            best = ev
    if best is None:
        return "outside rounds"
    return best.name if best.name != ROUND_SPAN else "harness"


@dataclasses.dataclass
class Reduction:
    window_s: float  # first traced round's start to last traced round's end
    busy_s: float  # union of device operations in the window, mean over devices
    rounds: int  # rounds wholly inside the traced slice
    device_ops: list[tuple[str, float]]  # top operations by total device time
    idle_gaps: list[tuple[str, float]]  # longest idle gaps, by host span


def reduce(trace: TraceData, top: int = 10) -> Reduction | None:
    """Busy union, top operations and attributed gaps over the traced rounds.

    Returns None where the slice holds no whole round or no device operation:
    a reader then finds nothing to read.
    """
    rounds = [ev for ev in trace.host if ev.name == ROUND_SPAN]
    if not rounds or not any(trace.devices.values()):
        return None
    lo, hi = min(ev.start for ev in rounds), max(ev.end for ev in rounds)
    busy_each, per_op = [], defaultdict(float)
    first_busy = None
    for plane, evs in sorted(trace.devices.items()):
        merged = union([(e.start, e.end) for e in evs])
        busy_each.append(covered(merged, lo, hi))
        for e in evs:
            per_op[e.name] += max(0.0, min(e.end, hi) - max(e.start, lo))
        if first_busy is None:
            first_busy = merged
    idle = sorted(((attribute(g, trace.host), g[1] - g[0])
                   for g in gaps(first_busy, lo, hi)), key=lambda x: -x[1])
    ops = sorted(((k, v) for k, v in per_op.items() if v > 0.0), key=lambda x: -x[1])
    return Reduction(window_s=hi - lo, busy_s=sum(busy_each) / len(busy_each),
                     rounds=len(rounds), device_ops=ops[:top], idle_gaps=idle[:top])


def device_ops_lines(plane: str, lines: list[str]) -> list[str]:
    """The lines of an accelerator plane that hold its operations: ``XLA
    Ops`` (one event per executed operation), else all of its lines."""
    if not plane.startswith("/device:"):
        return []
    return [ln for ln in lines if ln == "XLA Ops"] or lines


def load_xplane(path: "str | Path", device_lines=device_ops_lines) -> TraceData:
    """Device operations and host annotations from a profiler ``.xplane.pb``.

    ``device_lines(plane, line_names)`` names the lines whose events are
    device operations (a test on the CPU names the host thread that runs
    XLA's CPU programs). Host annotations are the events named
    ``bench.round`` or one of ``PROGRAM_SPANS`` on any ``/host:`` line.
    """
    import jax

    pd = jax.profiler.ProfileData.from_file(str(path))
    devices, host, lines = {}, [], {}
    keep = set(PROGRAM_SPANS) | {ROUND_SPAN}
    sec = lambda e: Event(e.name, e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9)
    for plane in pd.planes:
        plane_lines = list(plane.lines)
        names = [ln.name for ln in plane_lines]
        lines[plane.name] = names
        ops = set(device_lines(plane.name, names))
        if ops:
            devices[plane.name] = [sec(e) for ln in plane_lines if ln.name in ops
                                   for e in ln.events]
        if plane.name.startswith("/host:"):
            host.extend(sec(e) for ln in plane_lines for e in ln.events
                        if e.name in keep)
    return TraceData(devices, host, lines)


class ProfilerSlice:
    """Starts the profiler at the first round boundary past ``start_at``
    seconds into the window; ``stop()`` after the window ends it."""

    def __init__(self, out_dir: Path, start_at: float):
        self.dir = Path(out_dir)
        self.start_at = start_at
        self.started_round: int | None = None

    def before_round(self, i: int, elapsed: float) -> None:
        if self.started_round is None and elapsed >= self.start_at:
            import jax

            shutil.rmtree(self.dir, ignore_errors=True)
            jax.profiler.start_trace(str(self.dir))
            self.started_round = i

    def stop(self) -> Path | None:
        """Stop the profiler; the path of the trace it wrote, if any."""
        if self.started_round is None:
            return None
        import jax

        jax.profiler.stop_trace()
        found = sorted(glob.glob(str(self.dir / "plugins/profile/*/*.xplane.pb")))
        return Path(found[-1]) if found else None
