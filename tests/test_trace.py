"""Host sub-spans and device scopes of the fused closed loop.

The device loop's host phases (``closed_loop.pack`` / ``.dispatch``) are
tiled by named sub-spans, the one compiled program carries an ``obs.*``
scope per layer in its op metadata, and the span log stamps spans on the
profiler's own host clock.
"""
from __future__ import annotations

import gc
import time

import jax
import numpy as np
import pytest
from numpy.random import default_rng

from repro.configs.base import MeshConfig
from repro.core import M1, AdaptiveEngine, Workload, snap_to_grid
from repro.core.closed_loop import run_closed_loop
from repro.core.workload import FS_GRID, RS_GRID
from repro.fleet import FleetController
from repro.obs import trace as obs_trace

SUB_SPANS = {
    "closed_loop.pack": ("closed_loop.pack.arrivals", "closed_loop.pack.tables",
                         "closed_loop.pack.state"),
    "closed_loop.dispatch": ("closed_loop.dispatch.call",
                             "closed_loop.dispatch.wait",
                             "closed_loop.dispatch.fetch"),
}
DEVICE_SCOPES = ("obs.segment_event_loop", "obs.rates", "obs.arrive",
                 "obs.drain", "obs.finish", "obs.score", "obs.estimate",
                 "obs.detect", "obs.d_refresh")
SEGMENTS = 4


def _arrivals(seed: int = 5, n: int = 12, gap: float = 2e-5):
    rng = default_rng(seed)
    out, t = [], 0.0
    for k in range(SEGMENTS):
        for _ in range(n):
            fs = float(rng.choice(FS_GRID[10:14]))
            w = snap_to_grid(Workload(fs=fs, rs=float(rng.choice(RS_GRID[5:8])),
                                      data_total=fs * 6))
            t += float(rng.exponential(gap))
            out.append((t + 10.0 * k, w))
    return out


@pytest.fixture(scope="module")
def fleet_engine():
    """A fleet-controlled device loop, warmed so later runs compile nothing."""
    eng = AdaptiveEngine([M1] * 3, prior=0.0,
                         fleet=FleetController(mesh=MeshConfig()),
                         ring_capacity=256)
    eng.run(_arrivals(), segments=SEGMENTS, device_loop=True)
    return eng


def test_sub_spans_tile_pack_and_dispatch(fleet_engine):
    log = obs_trace.enable_tracing()
    gc.disable()  # a collection between two sub-spans is not what is tested
    try:
        for seed in (6, 7):
            fleet_engine.run(_arrivals(seed), segments=SEGMENTS, device_loop=True)
    finally:
        gc.enable()
        obs_trace.disable_tracing()
    for parent_name, child_names in SUB_SPANS.items():
        parents = [s for s in log.spans if s.name == parent_name]
        assert len(parents) == 2
        for p in parents:
            kids = sorted((s for s in log.spans if s.parent == p.id),
                          key=lambda s: s.start_ns)
            assert tuple(s.name for s in kids) == child_names
            assert all(s.depth == p.depth + 1 for s in kids)
            assert p.start_ns <= kids[0].start_ns
            assert kids[-1].end_ns <= p.end_ns
            covered = sum(s.end_ns - s.start_ns for s in kids)
            assert 0.95 * (p.end_ns - p.start_ns) <= covered <= p.end_ns - p.start_ns


def test_compiled_loop_names_every_obs_scope(fleet_engine):
    packed = fleet_engine._pack_device_loop(_arrivals(), SEGMENTS)
    assert packed.config.fleet  # the detector and the D re-blend's cond
    text = run_closed_loop.lower(*packed[:7]).compile().as_text()
    missing = [s for s in DEVICE_SCOPES if f"/{s}/" not in text]
    assert not missing


def test_span_log_lies_on_the_profiler_clock(tmp_path):
    log = obs_trace.enable_tracing()
    jax.profiler.start_trace(str(tmp_path))
    try:
        for _ in range(3):
            with obs_trace.span("t.outer"):
                with obs_trace.span("t.inner"):
                    time.sleep(0.003)
                time.sleep(0.002)
    finally:
        jax.profiler.stop_trace()
        obs_trace.disable_tracing()
    path = next(tmp_path.glob("plugins/profile/*/*.xplane.pb"))
    pd = jax.profiler.ProfileData.from_file(str(path))
    origin = next(int(v) for plane in pd.planes for k, v in plane.stats
                  if k == "profile_start_time")
    events = {name: sorted((origin + e.start_ns, origin + e.start_ns + e.duration_ns)
                           for plane in pd.planes if plane.name.startswith("/host:")
                           for ln in plane.lines for e in ln.events if e.name == name)
              for name in ("t.outer", "t.inner")}
    for name, evs in events.items():
        spans = sorted((s.start_ns, s.end_ns) for s in log.spans if s.name == name)
        assert len(spans) == len(evs) == 3
        off = np.abs(np.asarray(spans, np.float64) - np.asarray(evs, np.float64))
        assert off.max() < 0.5e6, (name, off)
