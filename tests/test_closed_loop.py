"""Device-loop equivalence: the fused closed loop vs the host oracle.

PR 7's tentpole compiles the whole observe -> estimate -> detect -> act
cycle into one ``lax.scan`` program (``core.closed_loop``); the
host-alternating ``AdaptiveEngine.run`` path is kept as the reference
oracle.  These tests pin the contract that makes that safe: *decisions* --
placements, queueing, split/evict events and their timing, requeue routing,
pool row maps, active masks -- are identical, and *float state* -- posterior
D, CUSUM statistics -- agrees to tolerance (the fused path fuses the same
arithmetic differently, so 1e-8-scale FMA drift is expected and absorbed by
the scheduler's score-margin tie collapse before it can reach a decision).
"""
from __future__ import annotations

import numpy as np
import pytest
from numpy.random import default_rng

import jax
import jax.numpy as jnp

from _hyp import given, settings, st
from repro.configs.base import MeshConfig
from repro.core import M1, AdaptiveEngine, Workload, snap_to_grid
from repro.core.workload import FS_GRID, RS_GRID
from repro.fleet import FleetController
from repro.obs import trace as obs_trace
from repro.telemetry import gradual_decay, stochastic_congestion

SEG_GAP = 10.0


def _segment(seed: int, n: int, gap: float = 2e-5):
    rng = default_rng(seed)
    out, t = [], 0.0
    for _ in range(n):
        fs = float(rng.choice(FS_GRID[10:14]))
        w = snap_to_grid(Workload(fs=fs, rs=float(rng.choice(RS_GRID[5:8])),
                                  data_total=fs * 6))
        t += float(rng.exponential(gap))
        out.append((t, w))
    return out


def _replay(seg, segments):
    return [(t + k * SEG_GAP, w) for k in range(segments) for t, w in seg]


def _run_pair(arrivals, segments, *, drift=None, m=3, decay=0.997, seed=11):
    """The same run down both paths; returns (host, device) triples."""
    out = []
    for device_loop in (False, True):
        servers = [M1] * m
        fleet = FleetController(mesh=MeshConfig())
        eng = AdaptiveEngine(servers, prior=0.0, decay=decay,
                             drift=drift([M1] * m) if drift else None,
                             fleet=fleet, ring_capacity=256)
        res = eng.run(arrivals, segments=segments, device_loop=device_loop)
        out.append((eng, fleet, res))
    return out


def _events(res):
    return [(ev.kind, ev.server, ev.segment)
            for evs in res.health for ev in evs]


def _assert_equivalent(host, dev, tol=1e-5):
    (h_eng, h_fleet, h_res), (d_eng, d_fleet, d_res) = host, dev
    # decisions: exact
    for k, (a, b) in enumerate(zip(h_res.segments, d_res.segments)):
        assert list(a.placements) == list(b.placements), f"segment {k}"
        assert list(a.was_queued) == list(b.was_queued), f"segment {k}"
    assert _events(h_res) == _events(d_res)
    assert list(h_res.n_obs) == list(d_res.n_obs)
    assert np.array_equal(h_fleet.pool.row_of, d_fleet.pool.row_of)
    assert np.array_equal(h_fleet.pool._read_row, d_fleet.pool._read_row)
    assert np.array_equal(h_fleet.active_mask(), d_fleet.active_mask())
    assert len(h_fleet.plans) == len(d_fleet.plans)
    assert h_eng.ring.total == d_eng.ring.total
    # float state: tolerance-bounded
    hD, dD = np.stack(h_fleet.current_D()), np.stack(d_fleet.current_D())
    np.testing.assert_allclose(dD, hD, atol=tol)
    for a, b in zip(h_fleet.detector.state, d_fleet.detector.state):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a), atol=tol)
    for a, b in zip(h_res.segments, d_res.segments):
        for x, y in zip(a.finish_times, b.finish_times):
            assert x == pytest.approx(y, rel=1e-4)


def test_stationary_equivalence():
    arrivals = _replay(_segment(11, 12), 6)
    host, dev = _run_pair(arrivals, 6)
    _assert_equivalent(host, dev)


def test_stochastic_congestion_equivalence():
    def drift(servers):
        return stochastic_congestion(servers, rate=0.3, seed=5, segments=6,
                                     servers=[1, 2])

    arrivals = _replay(_segment(7, 12), 6)
    host, dev = _run_pair(arrivals, 6, drift=drift)
    _assert_equivalent(host, dev)


def test_eviction_timing_equivalence():
    """The decisive case: a decaying server must be evicted in the SAME
    segment down both paths, with its in-flight work requeued identically
    (mirrors test_fleet's gradual-decay end-to-end scenario)."""
    segments, n_seg, failing = 6, 14, 1

    def drift(servers):
        return gradual_decay(servers, server=failing, rate=0.65, start=1,
                             segments=segments)

    arrivals = _replay(_segment(11, n_seg), segments)
    host, dev = _run_pair(arrivals, segments, drift=drift)
    _assert_equivalent(host, dev)
    evs = _events(host[2])
    evicts = [(s, seg) for kind, s, seg in evs if kind == "evict"]
    assert evicts and evicts[0][0] == failing, evs
    k_ev = evicts[0][1]
    # the requeue lands in the next segment, identically on both paths
    for _, _, res in (host, dev):
        assert len(res.segments[k_ev + 1].placements) > n_seg
        after = [p for r in res.segments[k_ev + 1:] for p in r.placements]
        assert failing not in after


@settings(max_examples=5, deadline=None)
@given(st.integers(min_value=0, max_value=10_000), st.integers(1, 8),
       st.integers(1, 3))
def test_chunk_invariance(seed, segments, n_seg):
    """Equivalence is not an artifact of one segment split: for arbitrary
    (segments, jobs-per-segment) chunkings of a stream, the fused loop and
    the host oracle place and queue identically."""
    arrivals = _replay(_segment(seed, n_seg), segments)
    host, dev = _run_pair(arrivals, segments, seed=seed)
    _assert_equivalent(host, dev)


def test_sparse_bank_tables_match_dense():
    """The fused path's sparse decay/co-update (first-occurrence slot
    folding) is the dense ``_bank_core`` arithmetic rearranged into the
    same in-order scatter sums -- the tables must match to float32
    round-off, at decay=1.0 (sparse fast path) and decay<1 alike."""
    from repro.fleet import FleetController as FC
    from repro.telemetry.estimator import _update_bank
    from repro.telemetry.log import RingBlock

    m, T, B = 4, 230, 12
    fleet = FC(mesh=MeshConfig())
    AdaptiveEngine([M1] * m, prior=0.0, fleet=fleet)  # binds the pool
    bank = fleet.pool.bank.stacked_state()
    rng = default_rng(0)
    ints = jnp.asarray(
        np.stack([rng.integers(0, m, B), rng.integers(0, T, B)], 1), jnp.int32)
    sc = jnp.asarray(rng.random((B, 4)) + 0.5, jnp.float32)
    co = jnp.asarray(rng.random((B, T)), jnp.float32)
    block = RingBlock(ints=ints, scalars=sc, co=co)
    for decay in (1.0, 0.997):
        hyp = dict(lr=0.6, decay=decay, step_damp=0.5, solo_eps=0.05,
                   max_lost_frac=0.5, use_pallas=False, interpret=False)
        dense, n_d = _update_bank(bank, block, **hyp)
        sparse, n_s = _update_bank(bank, block, sparse_tables=True, **hyp)
        assert int(n_d) == int(n_s)
        for name in dense._fields:
            np.testing.assert_allclose(
                np.asarray(getattr(sparse, name)),
                np.asarray(getattr(dense, name)),
                atol=1e-6, err_msg=f"{name} @ decay={decay}")


def test_engine_cache_survives_mask_change(monkeypatch):
    """PR-7 satellite: the segment-engine cache keys on (specs, active
    mask) while PackedDynamics caches on specs alone -- a drift schedule
    revisiting a world after an eviction changed the mask must not rebuild
    the dynamics tables."""
    import repro.core.engine as engine_mod

    builds = []
    orig = engine_mod.PackedDynamics.build

    def counting(specs, *a, **kw):
        builds.append(tuple(specs))
        return orig(specs, *a, **kw)

    monkeypatch.setattr(engine_mod.PackedDynamics, "build",
                        staticmethod(counting))
    segments, failing = 6, 1

    def drift(servers):
        return gradual_decay(servers, server=failing, rate=0.65, start=1,
                             segments=segments)

    servers = [M1] * 3
    fleet = FleetController(mesh=MeshConfig())
    eng = AdaptiveEngine(servers, prior=0.0, decay=0.997,
                         drift=drift(servers), fleet=fleet)
    res = eng.run(_replay(_segment(11, 14), segments), segments=segments)
    assert any(ev.kind == "evict" for evs in res.health for ev in evs)
    worlds = {tuple(eng.drift.specs_at(tuple(servers), k))
              for k in range(segments)}
    # one build per distinct world; the mask change after the eviction
    # re-keys the engine cache but reuses every cached dynamics table
    assert len(builds) == len(set(builds)) == len(worlds)


def _device_rounds(eng, plan, *, cold=False):
    """``plan``'s (arrivals, segments) as consecutive device-loop rounds of
    ``eng``; ``cold`` clears its loop-table cache before every round. Per
    round: the result, the program's inputs, final carry and ``ys`` on the
    host, and the cache counter after the round."""
    import repro.core.closed_loop as cl

    run, calls = cl.run_closed_loop, []

    def recording(*args):
        final, ys = run(*args)
        calls.append(jax.tree_util.tree_map(np.asarray, (args[:4], final, ys)))
        return final, ys

    rounds = []
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(cl, "run_closed_loop", recording)
        for arrivals, segments in plan:
            if cold:
                eng._loop_tables.clear()
                eng._loop_priors = None
            res = eng.run(arrivals, segments=segments, device_loop=True)
            rounds.append((res, calls[-1], dict(eng.loop_tables_stats)))
    return rounds


def _assert_rounds_identical(warm, cold):
    for (res_w, arrays_w, _), (res_c, arrays_c, _) in zip(warm, cold,
                                                          strict=True):
        assert res_w == res_c
        leaves_w, tree_w = jax.tree_util.tree_flatten(arrays_w)
        leaves_c, tree_c = jax.tree_util.tree_flatten(arrays_c)
        assert tree_w == tree_c
        for a, b in zip(leaves_w, leaves_c, strict=True):
            assert a.dtype == b.dtype
            np.testing.assert_array_equal(a, b)


def _fleet_engine(**kw):
    return AdaptiveEngine([M1] * 3, prior=0.0, decay=0.997, ring_capacity=256,
                          fleet=FleetController(mesh=MeshConfig()), **kw)


def test_loop_tables_built_once_per_engine():
    """Rounds after the first reuse the device loop's cluster, dynamics
    stack and priors; inputs, final carry, ``ys`` and results stay
    bit-identical to a twin that rebuilds them every round."""
    plan = [(_replay(_segment(seed, 8), 4), 4) for seed in (3, 4, 5)]
    log = obs_trace.enable_tracing()
    try:
        warm = _device_rounds(_fleet_engine(), plan)
    finally:
        obs_trace.disable_tracing()
    assert [stats for *_, stats in warm] == [
        {"hits": 0, "misses": 1}, {"hits": 1, "misses": 1},
        {"hits": 2, "misses": 1}]
    assert [s.attrs["cached"] for s in log.spans
            if s.name == "closed_loop.pack.tables"] == [False, True, True]
    cold = _device_rounds(_fleet_engine(), plan, cold=True)
    assert cold[-1][-1] == {"hits": 0, "misses": 3}
    _assert_rounds_identical(warm, cold)


def test_loop_tables_key_on_worlds_not_mask(monkeypatch):
    """A drift schedule's run builds its tables once per distinct tuple of
    per-segment worlds; the eviction it causes changes only the carried
    mask, so the second round takes hits alone, and decides as a twin
    that rebuilds every round."""
    import repro.core.engine as engine_mod

    clusters = []
    orig = engine_mod.PackedCluster.build

    def counting(servers, *a, **kw):
        clusters.append(tuple(servers))
        return orig(servers, *a, **kw)

    monkeypatch.setattr(engine_mod.PackedCluster, "build",
                        staticmethod(counting))
    segments, n_seg, failing = 6, 14, 1
    drift = gradual_decay([M1] * 3, server=failing, rate=0.65, start=1,
                          segments=segments)
    # 6 and 5 segments: two distinct world tuples (one compilation: both
    # pad to 8 segments of 14 arrivals)
    plan = [(_replay(_segment(seed, n_seg), k), k)
            for seed in (11, 12) for k in (segments, segments - 1)]
    eng = _fleet_engine(drift=drift)
    warm = _device_rounds(eng, plan)
    assert any(ev.kind == "evict" for ev in
               (ev for evs in warm[0][0].health for ev in evs))
    assert [stats for *_, stats in warm] == [
        {"hits": 0, "misses": 1}, {"hits": 0, "misses": 2},
        {"hits": 1, "misses": 2}, {"hits": 2, "misses": 2}]
    assert len(clusters) == 2
    assert len(eng._loop_tables) == 2
    assert len(eng._dyn_cache) == segments  # one table per distinct world
    cold = _device_rounds(_fleet_engine(drift=drift), plan, cold=True)
    assert cold[-1][-1] == {"hits": 0, "misses": 4}
    _assert_rounds_identical(warm, cold)


def test_device_loop_rejects_ragged_and_callbacks():
    eng = AdaptiveEngine([M1] * 2, prior=0.0, stream=True)
    arrivals = _replay(_segment(3, 3), 2)
    with pytest.raises(ValueError, match="divisible"):
        eng.run(arrivals, segments=4, device_loop=True)
    with pytest.raises(ValueError, match="on_segment"):
        eng.run(arrivals, segments=2, device_loop=True,
                on_segment=lambda *a: None)
    plain = AdaptiveEngine([M1] * 2, prior=0.0)
    with pytest.raises(ValueError, match="stream"):
        plain.run(arrivals, segments=2, device_loop=True)
