#!/usr/bin/env python3
"""Run the closed-loop consolidation engine once on a TPU chip, and check it.

    python chip_smoke.py [--seed N] [--four-chips]

With no option it needs one TPU chip. It drives the served path through the
entry points a user calls, on a fleet of 1,024 servers of the paper's two
Table I classes (M1 and M2 alternating, each a distinct machine):

  fleet   ``AdaptiveEngine(..., fleet=FleetController()).run(arrivals,
          segments=8, device_loop=True)`` with 256 Poisson arrivals per
          segment drawn from the (RS, FS) grid. The same trace through the
          host-alternating path (``device_loop=False``) must make the same
          placements, queue decisions and health actions.
  oracle  ``ConsolidationEngine(backend="jax")`` against the float64
          ``OnlineScheduler`` (``backend="numpy"``) on one trace at 256
          servers: the same placements and queue decisions, makespan within
          1e-3. Then the compiled Pallas scorer against the jnp scorer on the
          same trace: the same decisions.

``--four-chips`` runs only the sharded loop: ``run_closed_loop`` on the same
fleet and the first four segments of the same trace (four chips cost four
times as much per second; the fleet controller acts from the third segment
on), dense on one chip against the server axis split over four chips
(``ServerAxis.over_host_devices(4)``), which must agree as the multi-device
probe of tests/test_sharding_servers.py requires.

Each phase prints its compile and warm-run seconds, the device's peak memory
and whether its decisions matched. The last line of standard output is one
JSON object naming the device. The script exits non-zero, and prints no such
line, when JAX finds no TPU, when a phase raises, or when a comparison fails.
It runs in one process and starts no other.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
import traceback
from pathlib import Path

SRC = Path(__file__).resolve().parent / "src"

SERVERS = 1024  # the fleet: hundreds to ~12k servers in Hadoop deployments
PER_SEGMENT = 256  # arrivals per segment
SEGMENTS = 8
#: offered arrival rate (tasks/s): a segment's 256 arrivals land within
#: about one mean task duration, so the greedy co-locates several per server
RATE = 2.0e4
#: the oracle fleet is one Table I class (M1): same-class servers tie exactly
#: and break ties by index on both backends, while across classes the f32
#: engine treats score gaps below ``SCORE_MARGIN`` as ties where the float64
#: oracle does not (PERF.md, open questions)
ORACLE_SERVERS = 256
#: a burst of 4 MB - 16 MB tasks: half fit the LLC and take a server to
#: themselves, half stream past it and co-run, and the burst queues a few
ORACLE_ARRIVALS = 576
ORACLE_RATE = 1.0e6


def log(msg: str) -> None:
    print(msg, flush=True)


class CompileClock:
    """Seconds JAX spends lowering to XLA and compiling (``jax.monitoring``
    duration events); a persistent-cache hit skips the compile."""

    EVENTS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        self.seconds = 0.0

    def __call__(self, event: str, duration: float, **_):
        if event in self.EVENTS:
            self.seconds += duration

    def lap(self) -> float:
        s, self.seconds = self.seconds, 0.0
        return s


def fleet_servers(m: int, classes: str = "M1 M2"):
    """``m`` distinct machines cycling through the named Table I classes."""
    from repro.core import server

    kinds = [getattr(server, c) for c in classes.split()]
    return [dataclasses.replace(kinds[i % len(kinds)],
                                name=f"{kinds[i % len(kinds)].name}-{i}")
            for i in range(m)]


#: file sizes on the profiling grid (``FS_GRID`` slices): HDFS-block tasks of
#: 8 MB - 128 MB stream past the 6 MB LLC, so the cache criterion counts only
#: their request buffers and a server co-runs many; 4 MB and 6 MB working
#: sets fit the LLC, so a server takes one of those at a time
STREAMING_FS = slice(14, 19)
MIXED_FS = slice(12, 16)


def poisson_arrivals(seed: int, n: int, rate: float, fs_grid: slice = STREAMING_FS):
    """``n`` Poisson arrivals at ``rate`` tasks/s, each one pass over a file
    from ``FS_GRID[fs_grid]`` in requests of 16 KB - 512 KB (the grid's upper
    six request sizes)."""
    import numpy as np

    from repro.core import Workload, snap_to_grid
    from repro.core.workload import FS_GRID, RS_GRID

    rng = np.random.default_rng(seed)
    t = np.cumsum(rng.exponential(1.0 / rate, n))
    fs = rng.choice(np.asarray(FS_GRID[fs_grid]), n)
    rs = rng.choice(np.asarray(RS_GRID[4:]), n)
    return [(float(t[i]), snap_to_grid(Workload(fs=float(fs[i]), rs=float(rs[i]))))
            for i in range(n)]


def co_residency(results) -> float:
    """Mean number of tasks on a task's server at its placement, itself
    included."""
    seen, total = 0, 0
    for r in results:
        by_server: dict[int, list[tuple[float, float]]] = {}
        for p, t0, t1 in zip(r.placements, r.place_times, r.finish_times):
            if p is not None:
                by_server.setdefault(p, []).append((t0, t1))
        for runs in by_server.values():
            for t0, _ in runs:
                total += sum(1 for a, b in runs if a <= t0 < b)
                seen += 1
    return total / max(seen, 1)


def peak_bytes() -> int:
    import jax

    return int((jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use", 0))


def first_diff(a, b) -> str:
    for i, (x, y) in enumerate(zip(a, b)):
        if x != y:
            return f"first difference at {i}: {x} vs {y}"
    return f"lengths {len(a)} vs {len(b)}"


class Checks:
    def __init__(self):
        self.failed: list[str] = []

    def __call__(self, name: str, ok: bool, detail: str = "") -> None:
        log(f"  {name}: {'match' if ok else 'MISMATCH'}"
            + (f" ({detail})" if detail and not ok else ""))
        if not ok:
            self.failed.append(name)


def _decisions(res) -> dict:
    """A run's decisions, each tagged with its segment."""
    segs = list(enumerate(res.segments))
    return {
        "placements": [(k, p) for k, r in segs for p in r.placements],
        "queue decisions": [(k, q) for k, r in segs for q in r.was_queued],
        "health events": [(ev.segment, ev.kind, ev.server)
                          for evs in res.health for ev in evs],
    }


def fleet_phase(check: Checks, clock: CompileClock, *, seed: int,
                m: int = SERVERS, per_segment: int = PER_SEGMENT,
                segments: int = SEGMENTS, rate: float = RATE) -> None:
    from repro.core import AdaptiveEngine
    from repro.fleet import FleetController
    from repro.obs import trace as obs_trace

    servers = fleet_servers(m)
    arrivals = poisson_arrivals(seed, per_segment * segments, rate)
    log(f"fleet: {m} servers (M1/M2 alternating), {segments} segments x "
        f"{per_segment} Poisson arrivals at {rate:g} tasks/s")

    def run(device_loop: bool):
        engine = AdaptiveEngine(servers, prior=0.0, fleet=FleetController())
        spans = obs_trace.enable_tracing()
        clock.lap()
        t0 = time.perf_counter()
        res = engine.run(arrivals, segments=segments, device_loop=device_loop)
        wall = time.perf_counter() - t0
        obs_trace.disable_tracing()
        return res, wall, clock.lap(), spans.durations()

    runs = {}
    for label, device_loop in (("device_loop cold", True),
                               ("device_loop warm", True),
                               ("host-alternating", False)):
        res, wall, comp, spans = run(device_loop)
        runs[label] = res
        span_txt = " ".join(f"{k.split('.')[-1]}={v:.3f}s"
                            for k, v in spans.items() if k.startswith("closed_loop."))
        log(f"  {label}: run {wall:.3f}s, compile {comp:.3f}s"
            + (f", spans {span_txt}" if span_txt else ""))
    log(f"  mean co-residency at placement: "
        f"{co_residency(runs['device_loop cold'].segments):.3f} tasks/server; "
        f"queued {sum(sum(r.was_queued) for r in runs['device_loop cold'].segments)}"
        f" of {per_segment * segments}; health events "
        f"{sum(len(e) for e in runs['device_loop cold'].health)}")
    log(f"  peak_bytes_in_use: {peak_bytes()}")
    cold, warm, host = (_decisions(runs[k]) for k in
                        ("device_loop cold", "device_loop warm", "host-alternating"))
    check("fleet device_loop cold == warm", cold == warm)
    for name in cold:
        check(f"fleet device_loop vs host-alternating {name}",
              cold[name] == host[name], first_diff(cold[name], host[name]))


def oracle_phase(check: Checks, clock: CompileClock, *, seed: int,
                 m: int = ORACLE_SERVERS, n: int = ORACLE_ARRIVALS,
                 rate: float = ORACLE_RATE) -> None:
    from repro.core import ConsolidationEngine

    servers = fleet_servers(m, "M1")
    arrivals = poisson_arrivals(seed + 1, n, rate, MIXED_FS)
    log(f"oracle: {m} M1 servers, {n} Poisson arrivals at {rate:g} tasks/s")
    t0 = time.perf_counter()
    engines = {s: ConsolidationEngine(servers, scorer=s) for s in ("jnp", "pallas")}
    log(f"  profiling + packing: {time.perf_counter() - t0:.3f}s")

    t0 = time.perf_counter()
    ref = engines["jnp"].run(arrivals, backend="numpy")
    log(f"  numpy oracle: run {time.perf_counter() - t0:.3f}s, queued "
        f"{sum(ref.was_queued)} of {n}")
    out = {}
    for scorer, eng in engines.items():
        clock.lap()
        t0 = time.perf_counter()
        eng.run(arrivals, backend="jax")
        cold = time.perf_counter() - t0
        comp = clock.lap()
        t0 = time.perf_counter()
        out[scorer] = eng.run(arrivals, backend="jax")
        log(f"  jax scorer={scorer}: compile {comp:.3f}s, cold {cold:.3f}s, "
            f"warm run {time.perf_counter() - t0:.3f}s")
    log(f"  peak_bytes_in_use: {peak_bytes()}")
    jx = out["jnp"]
    check("oracle jax vs numpy placements", jx.placements == ref.placements,
          first_diff(jx.placements, ref.placements))
    check("oracle jax vs numpy queue decisions", jx.was_queued == ref.was_queued,
          first_diff(jx.was_queued, ref.was_queued))
    rel = abs(jx.makespan - ref.makespan) / max(abs(ref.makespan), 1e-30)
    check("oracle makespan within 1e-3", rel <= 1e-3, f"relative error {rel:.3g}")
    pl = out["pallas"]
    check("oracle pallas vs jnp scorer placements", pl.placements == jx.placements,
          first_diff(pl.placements, jx.placements))
    check("oracle pallas vs jnp scorer queue decisions",
          pl.was_queued == jx.was_queued, first_diff(pl.was_queued, jx.was_queued))


def four_chip_phase(check: Checks, clock: CompileClock, *, seed: int,
                    m: int = SERVERS, per_segment: int = PER_SEGMENT,
                    segments: int = SEGMENTS // 2, rate: float = RATE,
                    shards: int = 4) -> None:
    import jax
    import numpy as np

    from repro.core import AdaptiveEngine
    from repro.core.closed_loop import run_closed_loop
    from repro.distributed.server_axis import ServerAxis
    from repro.fleet import FleetController

    servers = fleet_servers(m)
    arrivals = poisson_arrivals(seed, per_segment * SEGMENTS,
                                rate)[:per_segment * segments]
    log(f"four chips: run_closed_loop, {m} servers, {segments} segments x "
        f"{per_segment} arrivals, dense on one chip vs {shards} shards")
    engine = AdaptiveEngine(servers, prior=0.0, fleet=FleetController())
    packed = engine._pack_device_loop(arrivals, segments)
    axis = ServerAxis.over_host_devices(shards)
    configs = {"dense": packed.config,
               f"{shards} shards": dataclasses.replace(packed.config, axis=axis)}
    out = {}
    for label, config in configs.items():
        t0 = time.perf_counter()
        compiled = run_closed_loop.lower(*packed[:6], config=config).compile()
        comp = time.perf_counter() - t0
        t0 = time.perf_counter()
        out[label] = jax.block_until_ready(compiled(*packed[:6]))
        log(f"  {label}: compile {comp:.3f}s, run {time.perf_counter() - t0:.3f}s")
    (fd, yd), (fs, ys_) = out.values()
    holders = sorted({sh.device.id for sh in fs.bank.L_t.addressable_shards})
    rows = sorted({sh.data.shape[0] for sh in fs.bank.L_t.addressable_shards})
    log(f"  sharded bank L_t: devices {holders}, rows per shard {rows}")
    check(f"all {shards} devices hold shards",
          len(holders) == shards and rows == [m // shards])
    fd, yd, fs, ys_ = (jax.tree_util.tree_map(np.asarray, t)
                       for t in (fd, yd, fs, ys_))
    for name, a, b in (("placements", yd.placement, ys_.placement),
                       ("row_map", fd.row_map, fs.row_map),
                       ("active", fd.active, fs.active),
                       ("split flags", yd.split_fired, ys_.split_fired),
                       ("evict flags", yd.evict_fired, ys_.evict_fired)):
        check(f"dense vs sharded {name}", np.array_equal(a, b))
    for name, a, b in (("posterior log_b", fd.bank.log_b, fs.bank.log_b),
                       ("posterior L_t", fd.bank.L_t, fs.bank.L_t),
                       ("detector stat", fd.det.stat, fs.det.stat),
                       ("detector level", fd.det.level, fs.det.level)):
        err = float(np.max(np.abs(a - b))) if a.size else 0.0
        check(f"dense vs sharded {name} within 1e-5", err <= 1e-5,
              f"max abs difference {err:.3g}")
    log(f"  peak_bytes_in_use (device 0): {peak_bytes()}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the server-axis sharded loop on four chips")
    args = ap.parse_args(argv)

    if not (SRC / "repro").is_dir():
        print(f"chip_smoke: the repro package is missing ({SRC / 'repro'})",
              file=sys.stderr)
        return 2
    sys.path.insert(0, str(SRC))
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX reports platform "
              f"{devices[0].platform!r}); nothing was run", file=sys.stderr)
        return 2
    need = 4 if args.four_chips else 1
    if len(devices) < need:
        print(f"chip_smoke: needs {need} TPU chips, JAX reports {len(devices)}",
              file=sys.stderr)
        return 2

    from repro.compile_cache import enable_compile_cache

    log(f"device: {devices[0].device_kind} x {len(devices)}; jax {jax.__version__}; "
        f"compile cache {enable_compile_cache()}")
    clock = CompileClock()
    jax.monitoring.register_event_duration_secs_listener(clock)
    check = Checks()
    phases = ([four_chip_phase] if args.four_chips
              else [fleet_phase, oracle_phase])
    for phase in phases:
        t0 = time.perf_counter()
        try:
            phase(check, clock, seed=args.seed)
        except Exception:  # noqa: BLE001 -- report, run the rest, exit non-zero
            traceback.print_exc()
            check.failed.append(f"{phase.__name__} raised")
        log(f"{phase.__name__}: {time.perf_counter() - t0:.3f}s")
    if check.failed:
        print(f"chip_smoke: FAILED: {check.failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
