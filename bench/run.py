#!/usr/bin/env python3
"""Run one benchmark cell of the closed-loop consolidation engine.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

``BENCHMARK.json`` names the cell's configuration (``bench/configs/<name>
.json``) and traffic mix (``bench/traffic/<name>.json``); each metric is
read by ``bench/metrics/<name>.py``; the limits of the correctness check are
in ``bench/limits/<workload>.json``. A run:

1. builds the scheduler for the configuration (set-up);
2. warms up on rounds of the cell's one shape until a round runs with no
   compile or cache-load event, then draws the window's pool of rounds;
3. measures for ``--seconds``: each round is one call of
   ``AdaptiveEngine.run(arrivals, segments, device_loop=True)``;
4. reads the device's peak memory, frees the scheduler, and replays every
   round through the float64 reference (``bench/reference.py``).

With ``--trace 0`` the last line of standard output carries the cell's
end-to-end metrics, with ``--trace 1`` its per-layer metrics: the program's
spans over every round, the profiler over a slice near the window's end.
The numbers compared with the reference, each beside its limit, are the
last lines of standard error and the last key of that line. The run exits
non-zero, printing no result, where JAX finds no accelerator or fewer chips
than the cell asks for. ``--control`` (never in a timed run) also lets the
reference decide in bfloat16 in the program's place, and prints how the
check judges it.
"""
from __future__ import annotations

import argparse
import importlib.util
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
BENCH = ROOT / "bench"
CACHE_DIR = ROOT / ".jax_cache"


def process_start() -> float:
    """Wall-clock time this process started (Linux), else now."""
    try:
        ticks = int(Path("/proc/self/stat").read_text().rsplit(")", 1)[1].split()[19])
        boot = next(float(ln.split()[1]) for ln in
                    Path("/proc/stat").read_text().splitlines() if ln.startswith("btime"))
        return boot + ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError, StopIteration):
        return time.time()


def log(msg: str) -> None:
    print(msg, flush=True)


def load_metric(name: str):
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def cell_metrics(bench: dict, workload: str, trace: bool) -> list[dict]:
    """The metrics this cell reports in this mode (``BENCHMARK.json``)."""
    e2e = [m for m in bench["end_to_end"]
           if workload in m.get("workloads", [workload])]
    if not trace:
        return e2e
    names = {m["name"] for m in e2e}
    return [m for m in bench["per_layer"]
            if (workload in m["workloads"] if "workloads" in m else m["moves"] in names)]


class Run:
    """Everything a metric reader may read about one run."""

    def __init__(self, **kw):
        self.__dict__.update(kw)


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--out", default=str(ROOT / ".bench_out"),
                    help="directory for per-round times and the trace slice")
    ap.add_argument("--control", action="store_true",
                    help="also judge the bfloat16 reference in the program's place")
    return ap.parse_args(argv)


def main(argv=None, *, require_accelerator: bool = True) -> int:
    """``require_accelerator=False`` is for tests on the CPU only."""
    t_process = process_start()
    args = parse(argv)
    bench_file = ROOT / "BENCHMARK.json"
    if not (ROOT / "src" / "repro").is_dir() or not bench_file.is_file():
        print("bench: the repro package or BENCHMARK.json is missing", file=sys.stderr)
        return 2
    bench = json.loads(bench_file.read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if args.workload not in cells:
        print(f"bench: no workload {args.workload!r} in BENCHMARK.json", file=sys.stderr)
        return 2
    cell = cells[args.workload]
    config = json.loads((BENCH / "configs" / f"{cell['config']}.json").read_text())
    limits = json.loads((BENCH / "limits" / f"{cell['name']}.json").read_text())

    # the compile cache lives in the checkout, at a fixed path, whatever the
    # environment says: two checkouts never share one
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(ROOT))
    import jax

    t_jax = time.time()
    devices = jax.devices()
    t_devices = time.time()
    if devices[0].platform != "cpu":
        # every program of a run goes to the cache, so a second run of the
        # cell in this checkout compiles nothing (CPU test runs keep none)
        jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    else:
        jax.config.update("jax_enable_compilation_cache", False)
    if require_accelerator and (devices[0].platform == "cpu"
                                or len(devices) < int(cell["chips"])):
        print(f"bench: needs {cell['chips']} accelerator chip(s); JAX reports "
              f"{len(devices)} {devices[0].platform} device(s); nothing was run",
              file=sys.stderr)
        return 2
    used = devices[: int(cell["chips"])]
    peaks = json.loads((BENCH / "peaks.json").read_text())
    if require_accelerator and used[0].device_kind not in peaks:
        print(f"bench: no peaks for device kind {used[0].device_kind!r} in "
              f"bench/peaks.json", file=sys.stderr)
        return 2
    hbm = peaks.get(used[0].device_kind, {}).get("hbm_bytes")

    from bench import generator, system, verify, window

    out_dir = Path(args.out) / args.workload
    out_dir.mkdir(parents=True, exist_ok=True)
    counter = window.CompileCounter().install()
    mix = generator.Mix.load(BENCH / "traffic" / f"{cell['traffic']}.json")
    log(f"cell {args.workload}: config {cell['config']}, traffic {cell['traffic']}, "
        f"{mix.segments} segments x {mix.per_segment} arrivals per round, seed "
        f"{args.seed}, {args.seconds:g} s, trace {args.trace}; device "
        f"{used[0].device_kind} x {len(used)}")

    sut = system.System(config, mix.segments)
    t_built = time.time()
    log(f"scheduler built: {t_built - t_process:.3f} s after process start (jax "
        f"imported at {t_jax - t_process:.3f} s, devices found at "
        f"{t_devices - t_process:.3f} s)")
    warm_rounds = mix.rounds(args.seed, 0, 8)
    warm_res, clean = window.warm_up(
        sut.run, [system.System.arrivals(r) for r in warm_rounds], counter, log)
    warm_rounds = warm_rounds[: len(warm_res)]
    n_pool = window.pool_size(args.seconds, min(clean))
    pool_rounds = mix.rounds(args.seed, len(warm_rounds), n_pool)
    pool = [system.System.arrivals(r) for r in pool_rounds]
    log(f"pool: {n_pool} rounds drawn from seed {args.seed} "
        f"(fastest clean warm round {min(clean):.4f} s), ready "
        f"{time.time() - t_process:.3f} s after process start")

    spans = prof = None
    run_round = sut.run
    if args.trace:
        from repro.obs import trace as obs_trace
        from bench import tracing

        slice_s = min(max(2.0, 2.5 * min(clean)), 0.5 * args.seconds)
        prof = tracing.ProfilerSlice(out_dir / "trace", args.seconds - slice_s)

        def run_round(arrivals):  # noqa: F811 -- the traced run's round
            with jax.profiler.TraceAnnotation(tracing.ROUND_SPAN):
                return sut.run(arrivals)

        spans = obs_trace.enable_tracing()
    setup_s = time.time() - t_process
    win = window.measure(
        run_round, pool, args.seconds, counter,
        decisions=lambda res: sum(len(s.placements) for s in res.segments),
        before_round=prof.before_round if prof else None)
    reduction = None
    if args.trace:
        obs_trace.disable_tracing()
        xplane = prof.stop()
        if xplane is not None:
            data = tracing.load_xplane(xplane)
            reduction = tracing.reduce(data)
            (out_dir / "trace_lines.json").write_text(json.dumps(data.lines, indent=1))
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0)) for d in used)
    if hbm:
        log(f"peak device memory {peak} bytes, {100.0 * peak / hbm:.3f}% of one "
            f"chip's {hbm} bytes")
    log(f"window: {win.rounds} rounds, {sum(win.decisions)} decisions in "
        f"{win.end_s[-1]:.4f} s, {win.compiles} compile/cache events")
    if args.trace:
        log(f"traced run decisions_per_s {win.decisions_per_s()!r} (tracing on)")
    (out_dir / f"rounds_seed{args.seed}_trace{args.trace}.json").write_text(json.dumps(
        {"round_s": win.round_s, "end_s": win.end_s, "decisions": win.decisions}))

    # the program's answers, then its state is freed before the reference runs
    rounds = warm_rounds + pool_rounds[: win.rounds]
    decs = [system.decisions(r, rnd) for r, rnd in zip(warm_res + win.results, rounds)]
    failed = sum(int((s["placement"] < 0).sum())
                 for d in decs[len(warm_res):] for s in d)
    del sut, warm_res, win.results, pool
    servers = system.server_list(config)
    judged = verify.judged_rounds(len(rounds), args.seed)
    t0 = time.time()
    numbers = verify.replay(config, servers, rounds, decs, judged)
    log(f"reference: {len(rounds)} rounds replayed, {len(judged)} judged, in "
        f"{time.time() - t0:.3f} s")

    run = Run(window=win, setup_s=setup_s, spans=spans, reduction=reduction,
              compiles=win.compiles, peak_bytes=peak, decisions=sum(win.decisions))
    metrics = {}
    for m in cell_metrics(bench, args.workload, bool(args.trace)):
        value = load_metric(m["name"]).read(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"platform": used[0].platform, "kind": used[0].device_kind,
              "count": len(used), "memory_peak_bytes": peak}
    result = {"correct": all(numbers[k] <= limits[k] for k in verify.NUMBERS),
              "attempted": sum(win.decisions), "failed": failed,
              "metrics": metrics, "device": device}
    if reduction is not None:
        device["busy_s"] = reduction.busy_s
        device["window_s"] = reduction.window_s
        result["breakdown"] = {"device_ops": [list(x) for x in reduction.device_ops],
                               "idle_gaps": [list(x) for x in reduction.idle_gaps]}
    if args.control:
        own = verify.run_own(config, servers, rounds, "bfloat16")
        ctrl = verify.replay(config, servers, rounds, own, judged)
        log("control (bfloat16 reference in the program's place): " + ", ".join(
            f"{k} {ctrl[k]!r} (limit {limits[k]!r})" for k in verify.NUMBERS)
            + f"; correct {all(ctrl[k] <= limits[k] for k in verify.NUMBERS)}")
    result["checks"] = {k: {"value": numbers[k], "limit": limits[k]}
                        for k in verify.NUMBERS}
    for k in verify.NUMBERS:
        print(f"check {k}: {numbers[k]!r} (limit {limits[k]!r})", file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
