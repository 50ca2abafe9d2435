"""Compile-cache guard: the hot loop must not re-trace.

PRs 4/5 bought the closed loop its speed by making every per-segment program
a jit-cache hit -- ``run_trace`` keyed on static (objective, scorer,
n_steps, telemetry), identity-stable scorers (``make_scorer`` lru-cached),
module-level jitted estimator/ring/detector programs. Any of those can
silently regress: a scorer closure rebuilt per segment, a hyperparameter
coerced to a fresh float becoming a new static key, a shape wobble in the
ring. Each regression costs a full XLA compile *per segment* instead of
per topology -- the difference between a control plane and a compiler loop.

:class:`CompileCacheGuard` snapshots ``_cache_size()`` of every tracked
jitted entry point around a block of work and reports the per-function
trace deltas. :func:`run_retrace_audit` uses it twice on a small 3-segment
``AdaptiveEngine`` (stream mode, 2 servers, equal segment shapes):

  warm run    at most one new trace per tracked function -- the segments
              share one shape, so a delta of 2+ means something in the
              cache key churns per segment;
  rerun       zero new traces anywhere, on the same engine instance --
              the acceptance criterion (0-recompile on a 3-segment rerun).
"""
from __future__ import annotations

from typing import Callable, Mapping

import numpy as np

from . import Finding


def tracked_functions() -> "dict[str, Callable]":
    """The jitted programs of the per-segment hot loop, by audit name.

    Everything here runs at least once per segment in stream mode; a cache
    miss on any of them is a per-segment compile. (Reads the live function
    objects at call time so reloads/tests see current state.)
    """
    from ..core import closed_loop, engine_jax
    from ..fleet import detect
    from ..telemetry import estimator, log

    return {
        "core.engine_jax.run_trace": engine_jax.run_trace,
        "core.closed_loop.run_closed_loop": closed_loop.run_closed_loop,
        "core.closed_loop.host_view": closed_loop.host_view,
        "telemetry.estimator._update_device": estimator._update_device,
        "telemetry.estimator._update_bank": estimator._update_bank,
        "telemetry.estimator._scatter_jnp_jit": estimator._scatter_jnp_jit,
        "telemetry.estimator._remap_rows": estimator._remap_rows,
        "telemetry.log._rows_from_trace_jit": log._rows_from_trace_jit,
        "telemetry.log._ring_write_trace": log._ring_write_trace,
        "telemetry.log._ring_write": log._ring_write,
        "telemetry.log._ring_write_contig": log._ring_write_contig,
        "fleet.detect._cusum_update": detect._cusum_update,
    }


class CompileCacheGuard:
    """Context manager over jit compile-cache size deltas.

    >>> with CompileCacheGuard() as guard:
    ...     engine.run(arrivals, segments=3)
    >>> guard.deltas  # {'core.engine_jax.run_trace': 1, ...} new traces
    >>> guard.assert_max(0)  # raises on any recompile

    Tracks :func:`tracked_functions` by default; pass ``functions`` (name ->
    jitted callable exposing ``_cache_size``) to guard something else, e.g.
    a single function in a unit test.
    """

    def __init__(self, functions: "Mapping[str, Callable] | None" = None):
        self._functions = dict(functions) if functions is not None else tracked_functions()
        self._before: dict[str, int] = {}
        self.deltas: dict[str, int] = {}

    @staticmethod
    def _size(fn) -> int:
        return int(fn._cache_size())

    def __enter__(self) -> "CompileCacheGuard":
        self._before = {name: self._size(f) for name, f in self._functions.items()}
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        self.deltas = {
            name: self._size(f) - self._before[name]
            for name, f in self._functions.items()}

    def new_traces(self) -> dict[str, int]:
        """Only the functions that actually re-traced (delta > 0)."""
        return {k: v for k, v in self.deltas.items() if v > 0}

    def assert_max(self, limit: int, context: str = "") -> None:
        bad = {k: v for k, v in self.deltas.items() if v > limit}
        if bad:
            where = f" during {context}" if context else ""
            raise AssertionError(
                f"compile-cache guard{where}: traces exceeded limit {limit}: "
                + ", ".join(f"{k} (+{v})" for k, v in sorted(bad.items())))


def _small_adaptive_engine():
    """A 2-server streaming AdaptiveEngine at audit scale (compiles in
    seconds on CPU; every per-segment program still runs)."""
    from ..core.engine import AdaptiveEngine
    from ..core.server import M1, M2

    return AdaptiveEngine([M1, M2], prior=0.0, scorer="jnp", stream=True,
                          ring_capacity=64)


def _audit_arrivals(n: int = 24):
    """n arrivals over a few grid types, divisible into equal segments (all
    segments then share one (m, n) trace shape -- one compile covers all)."""
    from ..core.workload import FS_GRID, RS_GRID, Workload, snap_to_grid

    arrivals = []
    for i in range(n):
        w = snap_to_grid(Workload(
            fs=FS_GRID[(7 * i) % len(FS_GRID)], rs=RS_GRID[i % len(RS_GRID)],
            data_total=64e6))
        arrivals.append((0.25 * i, w))
    return arrivals


def run_retrace_audit(stats: "dict | None" = None,
                      segments: int = 3) -> list[Finding]:
    """Warm-run + rerun the reference adaptive loop under the guard."""
    arrivals = _audit_arrivals(n=8 * segments)
    engine = _small_adaptive_engine()

    with CompileCacheGuard() as warm:
        engine.run(arrivals, segments=segments)
    with CompileCacheGuard() as rerun:
        engine.run(arrivals, segments=segments)

    # device-resident loop: one compile must cover every segment count in
    # an S_cap bucket. The warm run compiles at segments=4; reruns at 2 and
    # 3 segments (same power-of-two bucket, same per-segment shape) must add
    # zero traces anywhere -- a delta means the padded-scan shapes or the
    # static config churn with the segment count.
    n_seg = 8
    dev_engine = _small_adaptive_engine()
    dev_arrivals = _audit_arrivals(n=n_seg * 4)
    with CompileCacheGuard() as dev_warm:
        dev_engine.run(dev_arrivals, segments=4, device_loop=True)
    with CompileCacheGuard() as dev_rerun:
        dev_engine.run(dev_arrivals[:n_seg * 2], segments=2, device_loop=True)
        dev_engine.run(dev_arrivals[:n_seg * 3], segments=3, device_loop=True)

    # metrics plane: ``metrics=True`` is one more static key on run_trace /
    # one more field in the ClosedLoopConfig hash, so the first metrics run
    # legitimately traces once per function -- after that the instrumented
    # program must be exactly as cache-stable as the bare one. A metrics
    # rerun (host alternating segments, then device loops at 2/3 segments
    # inside the warm 4-segment bucket) must add ZERO traces.
    obs_engine = _small_adaptive_engine()
    with CompileCacheGuard() as obs_warm:
        obs_engine.run(arrivals, segments=segments, metrics=True)
    with CompileCacheGuard() as obs_rerun:
        obs_engine.run(arrivals, segments=segments, metrics=True)
    obs_dev = _small_adaptive_engine()
    with CompileCacheGuard() as obs_dev_warm:
        obs_dev.run(dev_arrivals, segments=4, device_loop=True, metrics=True)
    with CompileCacheGuard() as obs_dev_rerun:
        obs_dev.run(dev_arrivals[:n_seg * 2], segments=2, device_loop=True,
                    metrics=True)
        obs_dev.run(dev_arrivals[:n_seg * 3], segments=3, device_loop=True,
                    metrics=True)

    # decision recorder: ``record=True`` is one more static key on run_trace
    # / one more field in the ClosedLoopConfig hash, same contract as the
    # metrics plane -- the first recorder run traces once, after which
    # recorder-on reruns (host alternating, then device loops at 2/3
    # segments inside the warm 4-segment bucket) must add ZERO traces. The
    # ring riding the carry moves values, never shapes.
    rec_engine = _small_adaptive_engine()
    with CompileCacheGuard() as rec_warm:
        rec_engine.run(arrivals, segments=segments, record=True)
    with CompileCacheGuard() as rec_rerun:
        rec_engine.run(arrivals, segments=segments, record=True)
    rec_dev = _small_adaptive_engine()
    with CompileCacheGuard() as rec_dev_warm:
        rec_dev.run(dev_arrivals, segments=4, device_loop=True, record=True)
    with CompileCacheGuard() as rec_dev_rerun:
        rec_dev.run(dev_arrivals[:n_seg * 2], segments=2, device_loop=True,
                    record=True)
        rec_dev.run(dev_arrivals[:n_seg * 3], segments=3, device_loop=True,
                    record=True)

    # sharded loop: a ServerAxis over a 1-device mesh runs the whole scan
    # under shard_map -- same static config hash rules as dense (the axis is
    # a frozen dataclass, hashable by mesh value). The warm run pays one
    # trace; an identical rerun must add ZERO -- a delta means the axis (or
    # something it carries) churns the jit key per call, i.e. every segment
    # of a 10k-server run would recompile.
    from .jaxpr_audit import _build_closed_loop_sharded
    import jax

    sh_fn, sh_args = _build_closed_loop_sharded()
    with CompileCacheGuard() as sh_warm:
        jax.block_until_ready(sh_fn(*sh_args))
    with CompileCacheGuard() as sh_rerun:
        jax.block_until_ready(sh_fn(*sh_args))

    findings = [
        Finding("retrace", "per-segment-retrace", name,
                f"{delta} traces in a warm {segments}-segment run of one "
                "shape (expected at most 1: the cache key churns per segment)")
        for name, delta in sorted(warm.new_traces().items()) if delta > 1
    ] + [
        Finding("retrace", "rerun-recompile", name,
                f"{delta} new traces on an identical rerun (expected 0: "
                "the warm run should have populated every cache)")
        for name, delta in sorted(rerun.new_traces().items())
    ] + [
        Finding("retrace", "device-loop-recompile", name,
                f"{delta} new traces running 2- and 3-segment device loops "
                "after a warm 4-segment run (expected 0: segment counts in "
                "one S_cap bucket share a compilation)")
        for name, delta in sorted(dev_rerun.new_traces().items())
    ] + [
        Finding("retrace", "metrics-retrace", name,
                f"{delta} traces in a warm metrics-on {segments}-segment run "
                "(expected at most 1: the MetricFrame ops churn the cache "
                "key per segment)")
        for name, delta in sorted(obs_warm.new_traces().items()) if delta > 1
    ] + [
        Finding("retrace", "metrics-rerun-recompile", name,
                f"{delta} new traces on an identical metrics-on rerun "
                "(expected 0: instrumentation must not erode cache stability)")
        for name, delta in sorted(obs_rerun.new_traces().items())
    ] + [
        Finding("retrace", "metrics-device-loop-recompile", name,
                f"{delta} new traces running metrics-on 2- and 3-segment "
                "device loops after a warm metrics-on 4-segment run "
                "(expected 0)")
        for name, delta in sorted(obs_dev_rerun.new_traces().items())
    ] + [
        Finding("retrace", "recorder-retrace", name,
                f"{delta} traces in a warm recorder-on {segments}-segment "
                "run (expected at most 1: the decision-ring ops churn the "
                "cache key per segment)")
        for name, delta in sorted(rec_warm.new_traces().items()) if delta > 1
    ] + [
        Finding("retrace", "recorder-rerun-recompile", name,
                f"{delta} new traces on an identical recorder-on rerun "
                "(expected 0: the flight recorder must not erode cache "
                "stability)")
        for name, delta in sorted(rec_rerun.new_traces().items())
    ] + [
        Finding("retrace", "recorder-device-loop-recompile", name,
                f"{delta} new traces running recorder-on 2- and 3-segment "
                "device loops after a warm recorder-on 4-segment run "
                "(expected 0)")
        for name, delta in sorted(rec_dev_rerun.new_traces().items())
    ] + [
        Finding("retrace", "sharded-loop-recompile", name,
                f"{delta} new traces rerunning the warm sharded closed loop "
                "(expected 0: the ServerAxis static key must be call-stable)")
        for name, delta in sorted(sh_rerun.new_traces().items())
    ]
    if stats is not None:
        stats["retrace"] = {
            "segments": segments,
            "warm_traces": warm.new_traces(),
            "rerun_traces": rerun.new_traces(),
            "rerun_total": int(np.sum(list(rerun.deltas.values()) or [0])),
            "device_warm_traces": dev_warm.new_traces(),
            "device_rerun_traces": dev_rerun.new_traces(),
            "metrics_warm_traces": obs_warm.new_traces(),
            "metrics_rerun_traces": obs_rerun.new_traces(),
            "metrics_rerun_total": int(
                np.sum(list(obs_rerun.deltas.values()) or [0])),
            "metrics_device_warm_traces": obs_dev_warm.new_traces(),
            "metrics_device_rerun_traces": obs_dev_rerun.new_traces(),
            "recorder_warm_traces": rec_warm.new_traces(),
            "recorder_rerun_traces": rec_rerun.new_traces(),
            "recorder_rerun_total": int(
                np.sum(list(rec_rerun.deltas.values()) or [0])),
            "recorder_device_warm_traces": rec_dev_warm.new_traces(),
            "recorder_device_rerun_traces": rec_dev_rerun.new_traces(),
            "sharded_warm_traces": sh_warm.new_traces(),
            "sharded_rerun_traces": sh_rerun.new_traces(),
            "sharded_rerun_total": int(
                np.sum(list(sh_rerun.deltas.values()) or [0])),
        }
    return findings
