"""Benchmark harness: one module per paper table/figure (DESIGN.md §7).

    PYTHONPATH=src python -m benchmarks.run [--only fig9] [--smoke] [--out-dir .]
                                           [--compare <baseline-dir>]

Output format: ``name,us_per_call,derived`` on stdout, plus one
``BENCH_<suite>.json`` per suite so the performance trajectory is tracked
across PRs. Each file records ``{"suite", "meta": {"commit", "smoke"},
"records": [{name, value, unit, meta}, ...]}`` -- the git commit stamps
every suite so a regression can be bisected straight from the JSON, and
``smoke`` marks reduced-size CI runs that must not be compared against full
runs. ``--smoke`` is the PR-gate mode: every module shrinks its problem
sizes enough to finish in CI while still exercising the full code path.

``--compare <dir>`` diffs each freshly written suite against the
``BENCH_<suite>.json`` in ``dir`` and exits non-zero on regression:
time-unit records (``us_*``) past the suite's relative threshold
(:data:`COMPARE_THRESHOLDS`), any ``bool`` record flipping, or any
baseline record missing from the new run (a silently dropped gate is a
regression too). Non-time value records are reported informationally only
-- regret/ratio trajectories move for legitimate reasons and have their own
in-suite gates. Smoke baselines only compare against smoke runs.
"""
from __future__ import annotations

import argparse
import inspect
import json
import pathlib
import subprocess
import sys
import traceback

from . import (
    adaptive_regret,
    closed_loop,
    fig6_llc_loss,
    fig9_greedy_vs_optimal,
    fig12_single_workload,
    fig34_consolidation,
    fleet_health,
    obs_overhead,
    roofline_table,
    scale_scheduler,
    table2_greedy_example,
    telemetry_throughput,
)

MODULES = [
    ("fig12", fig12_single_workload),
    ("fig34", fig34_consolidation),
    ("fig6", fig6_llc_loss),
    ("table2", table2_greedy_example),
    ("fig9", fig9_greedy_vs_optimal),
    ("scale", scale_scheduler),
    ("adaptive", adaptive_regret),
    ("telemetry", telemetry_throughput),
    ("fleet", fleet_health),
    ("roofline", roofline_table),
    ("closedloop", closed_loop),
    ("obs", obs_overhead),
]


#: default relative regression threshold for time-unit records: smoke CI
#: shares a noisy runner, so the gate is generous -- it exists to catch
#: order-of-magnitude cliffs (an accidental retrace, a host sync in the hot
#: loop), not single-digit-percent drift
COMPARE_DEFAULT_THRESHOLD = 0.5
#: per-suite overrides: suites timing very short kernels (sub-100us) see
#: proportionally more scheduler noise
COMPARE_THRESHOLDS = {
    "scale": 0.75,
    "telemetry": 0.75,
    "obs": 0.75,
    "closedloop": 0.75,
}
#: units where the value is a duration and bigger means slower
TIME_UNITS = ("us_per_call", "us_per_segment", "us_total")


def compare_suite(suite: str, baseline: dict, current: dict) -> "list[str]":
    """Diff one suite's records against a baseline; returns regression
    messages (empty = pass)."""
    failures: list[str] = []
    if bool(baseline.get("meta", {}).get("smoke")) != bool(
            current.get("meta", {}).get("smoke")):
        return [f"{suite}: smoke flag differs from baseline -- full and "
                f"smoke runs are not comparable"]
    base = {r["name"]: r for r in baseline.get("records", [])}
    cur = {r["name"]: r for r in current.get("records", [])}
    thr = COMPARE_THRESHOLDS.get(suite, COMPARE_DEFAULT_THRESHOLD)
    for name, b in base.items():
        c = cur.get(name)
        if c is None:
            failures.append(f"{suite}/{name}: present in baseline, missing "
                            f"from this run")
            continue
        bv, cv = float(b["value"]), float(c["value"])
        if b.get("unit") == "bool":
            if cv != bv:
                failures.append(
                    f"{suite}/{name}: gate flipped {bv:g} -> {cv:g}")
        elif b.get("unit") in TIME_UNITS and bv > 0:
            rel = cv / bv - 1.0
            if rel > thr:
                failures.append(
                    f"{suite}/{name}: {bv:g} -> {cv:g} {b['unit']} "
                    f"(+{rel:.0%} exceeds the +{thr:.0%} gate)")
    return failures


def git_commit() -> str:
    try:
        return subprocess.run(
            ["git", "rev-parse", "HEAD"],
            cwd=pathlib.Path(__file__).resolve().parent,
            capture_output=True, text=True, check=True,
        ).stdout.strip()
    except (OSError, subprocess.CalledProcessError):
        return "unknown"


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None, help="run benches whose tag contains this")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced problem sizes (the CI PR gate)")
    ap.add_argument("--profile", action="store_true",
                    help="modules that support it dump a jax.profiler trace "
                         "(closed_loop: one warm device-loop dispatch)")
    ap.add_argument("--out-dir", default=str(pathlib.Path(__file__).resolve().parents[1]),
                    help="directory for BENCH_<suite>.json records")
    ap.add_argument("--compare", default=None, metavar="BASELINE_DIR",
                    help="diff each suite against BASELINE_DIR/BENCH_<suite>"
                         ".json and exit non-zero on regression")
    args = ap.parse_args()
    from repro.compile_cache import enable_compile_cache

    print(f"compile cache: {enable_compile_cache()}", file=sys.stderr)
    out_dir = pathlib.Path(args.out_dir)
    out_dir.mkdir(parents=True, exist_ok=True)
    meta = {"commit": git_commit(), "smoke": bool(args.smoke)}

    if args.smoke:
        # refuse to measure an impure hot path: the same gate CI runs as the
        # static-analysis job (unbaselined findings -> SystemExit)
        from repro.analysis import preflight

        preflight()
        print("analysis preflight: clean")

    print("name,us_per_call,derived")

    records: list[dict] = []

    def emit(name: str, us: float, derived: str, unit: str = "us_per_call"):
        print(f"{name},{us:.2f},{derived}")
        sys.stdout.flush()
        records.append({"name": name, "value": round(us, 3), "unit": unit,
                        "meta": derived})

    failures = []
    regressions: list[str] = []
    for tag, mod in MODULES:
        if args.only and args.only not in tag:
            continue
        records = []
        try:
            kwargs = {}
            if "profile" in inspect.signature(mod.run).parameters:
                kwargs["profile"] = args.profile
            mod.run(emit, smoke=args.smoke, **kwargs)
        except Exception as e:  # noqa: BLE001 -- report and continue
            failures.append((tag, e))
            traceback.print_exc()
            emit(f"{tag}/ERROR", 0.0, repr(e)[:120])
        path = out_dir / f"BENCH_{tag}.json"
        suite = {"suite": tag, "meta": meta, "records": records}
        path.write_text(json.dumps(suite, indent=2) + "\n")
        if args.compare:
            base_path = pathlib.Path(args.compare) / f"BENCH_{tag}.json"
            if not base_path.exists():
                print(f"compare: no baseline for {tag} "
                      f"({base_path}), skipping")
                continue
            found = compare_suite(tag, json.loads(base_path.read_text()),
                                  suite)
            regressions.extend(found)
            status = "ok" if not found else f"{len(found)} REGRESSION(S)"
            print(f"compare: {tag:<12} vs {base_path}: {status}")
    for r in regressions:
        print(f"REGRESSION: {r}", file=sys.stderr)
    if failures:
        raise SystemExit(f"{len(failures)} benchmark modules failed: {[t for t, _ in failures]}")
    if regressions:
        raise SystemExit(
            f"{len(regressions)} benchmark regressions vs {args.compare}")


if __name__ == "__main__":
    main()
