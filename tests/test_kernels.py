"""Pallas kernels vs their pure-jnp oracles (ref.py), interpret mode.

Each kernel is swept over shapes/dtypes per the assignment:
'sweep shapes/dtypes and assert_allclose against the ref.py pure-jnp oracle'.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import M1, PAPER_CLUSTER, PackedCluster, profile_pairwise_fast
from repro.kernels import ops, ref
from repro.kernels.consolidation import consolidation_scores


def _gqa_ref(q, k, v, causal, q_offset=0):
    B, Sq, H, dh = q.shape
    Hkv = k.shape[2]
    G = H // Hkv
    kx = jnp.repeat(k, G, axis=2).transpose(0, 2, 1, 3).reshape(B * H, -1, dh)
    vx = jnp.repeat(v, G, axis=2).transpose(0, 2, 1, 3).reshape(B * H, -1, dh)
    qx = q.transpose(0, 2, 1, 3).reshape(B * H, Sq, dh)
    out = ref.attention_ref(qx, kx, vx, causal=causal, q_offset=q_offset)
    return out.reshape(B, H, Sq, dh).transpose(0, 2, 1, 3)


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5), (jnp.bfloat16, 2e-2)])
@pytest.mark.parametrize(
    "B,Sq,Skv,H,Hkv,dh,causal",
    [
        (1, 64, 64, 2, 2, 32, True),
        (2, 128, 128, 4, 2, 64, True),
        (1, 64, 128, 2, 1, 32, False),  # cross-attention-like
        (2, 1, 128, 4, 4, 32, True),  # decode: Sq=1
    ],
)
def test_flash_attention_sweep(B, Sq, Skv, H, Hkv, dh, causal, dtype, tol):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, Sq, H, dh), dtype)
    k = jax.random.normal(ks[1], (B, Skv, Hkv, dh), dtype)
    v = jax.random.normal(ks[2], (B, Skv, Hkv, dh), dtype)
    q_offset = Skv - Sq if causal else 0
    out = ops.gqa_flash_attention(q, k, v, causal=causal, q_offset=q_offset,
                                  mode="interpret", block_q=32, block_k=32)
    want = _gqa_ref(q, k, v, causal, q_offset)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), atol=tol, rtol=tol)


@pytest.mark.parametrize("B,S,H,dh,chunk", [(1, 32, 1, 8, 8), (2, 64, 2, 16, 16), (1, 48, 2, 16, 16)])
def test_rwkv6_scan_sweep(B, S, H, dh, chunk):
    ks = jax.random.split(jax.random.PRNGKey(1), 5)
    r = jax.random.normal(ks[0], (B, S, H, dh))
    k = jax.random.normal(ks[1], (B, S, H, dh))
    v = jax.random.normal(ks[2], (B, S, H, dh))
    wlog = -jnp.exp(jax.random.normal(ks[3], (B, S, H, dh)) * 0.5)
    u = jax.random.normal(ks[4], (H, dh)) * 0.1
    s0 = jnp.zeros((B, H, dh, dh))
    y, sT = ops.rwkv6_wkv(r, k, v, wlog, u, s0, chunk=chunk, mode="interpret")
    fold = lambda x: x.transpose(0, 2, 1, 3).reshape(B * H, S, dh)
    yr, sr = ref.rwkv6_ref(fold(r), fold(k), fold(v), fold(wlog),
                           jnp.broadcast_to(u[None], (B, H, dh)).reshape(B * H, dh),
                           s0.reshape(B * H, dh, dh))
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr.reshape(B, H, S, dh).transpose(0, 2, 1, 3)),
                               atol=5e-4, rtol=1e-3)
    np.testing.assert_allclose(np.asarray(sT.reshape(B * H, dh, dh)), np.asarray(sr),
                               atol=5e-4, rtol=1e-3)


def test_rwkv6_scan_nonzero_initial_state():
    B, S, H, dh = 1, 32, 1, 8
    ks = jax.random.split(jax.random.PRNGKey(2), 5)
    r, k, v = (jax.random.normal(ks[i], (B, S, H, dh)) for i in range(3))
    wlog = -jnp.exp(jax.random.normal(ks[3], (B, S, H, dh)) * 0.3)
    u = jax.random.normal(ks[4], (H, dh)) * 0.1
    s0 = jax.random.normal(ks[0], (B, H, dh, dh))
    y, sT = ops.rwkv6_wkv(r, k, v, wlog, u, s0, chunk=8, mode="interpret")
    fold = lambda x: x.transpose(0, 2, 1, 3).reshape(B * H, S, dh)
    yr, sr = ref.rwkv6_ref(fold(r), fold(k), fold(v), fold(wlog),
                           u.reshape(B * H, dh), s0.reshape(B * H, dh, dh))
    np.testing.assert_allclose(np.asarray(y[0, :, 0]), np.asarray(yr[0]), atol=5e-4, rtol=1e-3)


@pytest.mark.parametrize("B,S,E,N,chunk,eblock", [(1, 32, 16, 4, 8, 8), (2, 64, 32, 8, 16, 16)])
def test_mamba_scan_sweep(B, S, E, N, chunk, eblock):
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    da = jnp.exp(-jnp.abs(jax.random.normal(ks[0], (B, S, E, N))))
    dbu = jax.random.normal(ks[1], (B, S, E, N)) * 0.1
    c = jax.random.normal(ks[2], (B, S, N))
    h0 = jnp.zeros((B, E, N))
    y, hT = ops.mamba_ssm_scan(da, dbu, c, h0, chunk=chunk, eblock=eblock, mode="interpret")
    yr, hr = ref.mamba_ref(da, dbu, c)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(hT), np.asarray(hr), atol=1e-5, rtol=1e-5)


def test_consolidation_scores_vs_ref_and_model():
    servers = list(PAPER_CLUSTER)[:2]
    Ds = [profile_pairwise_fast(s) for s in servers]
    cluster = PackedCluster.build(servers, Ds, alpha=1.3)
    counts = jnp.zeros((2, cluster.T)).at[0, 5].add(2).at[1, 40].add(1)
    wtypes = jnp.asarray([3, 77, 130, 229], jnp.int32)
    fs_res = cluster.resident * cluster.fs[None]
    cache, maxd = consolidation_scores(counts, cluster.D, cluster.rs, fs_res,
                                       cluster.llc_budget, wtypes, interpret=True)
    cr, mr = ref.consolidation_scores_ref(
        counts, cluster.D, np.asarray(cluster.rs), np.asarray(cluster.fs),
        np.asarray(cluster.llc_budget), np.asarray(cluster.resident), wtypes)
    np.testing.assert_allclose(np.asarray(cache), np.asarray(cr), atol=1e-5)
    np.testing.assert_allclose(np.asarray(maxd), np.asarray(mr), atol=1e-5)


@pytest.mark.parametrize("m,Q", [
    (3, 1),  # one arrival: the engine's per-arrival pick
    (5, 16),  # a drain window, one candidate block
    (2, 300),  # a full queue scan: two candidate blocks, the last one padded
])
def test_consolidation_scores_blocks_vs_ref_and_jnp(m, Q):
    """Candidate blocking (BLOCK_Q rows per program, padded to a multiple)
    keeps the kernel on the float64 reference and on the jnp scorer."""
    import dataclasses

    from repro.core import M2
    from repro.core.binpack_jax import score_candidates_jnp

    servers = [dataclasses.replace([M1, M2][i % 2], name=f"s{i}")
               for i in range(m)]
    Ds = [profile_pairwise_fast([M1, M2][i % 2]) for i in range(m)]
    cluster = PackedCluster.build(servers, Ds, alpha=1.3)
    rng = np.random.default_rng(m * 100 + Q)
    counts = np.zeros((m, cluster.T), np.float32)
    for s in range(m):
        counts[s, rng.integers(0, cluster.T, 3)] += 1.0
    counts = jnp.asarray(counts)
    wtypes = jnp.asarray(rng.integers(0, cluster.T, Q).astype(np.int32))
    fs_res = cluster.resident * cluster.fs[None]
    cache, maxd = consolidation_scores(
        counts, cluster.D, cluster.rs, fs_res, cluster.llc_budget, wtypes,
        interpret=True)
    assert cache.shape == (Q, m) and maxd.shape == (Q, m)
    cr, mr = ref.consolidation_scores_ref(
        counts, cluster.D, np.asarray(cluster.rs), np.asarray(cluster.fs),
        np.asarray(cluster.llc_budget), np.asarray(cluster.resident), wtypes)
    np.testing.assert_allclose(np.asarray(cache), np.asarray(cr), atol=1e-5)
    np.testing.assert_allclose(np.asarray(maxd), np.asarray(mr), atol=1e-5)
    cj, mj = score_candidates_jnp(cluster, counts, wtypes)
    np.testing.assert_allclose(np.asarray(cache), np.asarray(cj), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(maxd), np.asarray(mj), atol=1e-6)


@pytest.mark.parametrize(
    "B,T,block_b",
    [
        (1, 16, 128),  # single observation
        (7, 230, 128),  # smaller than one block (padding path)
        (128, 230, 64),  # multiple full blocks
        (300, 64, 128),  # partial last block (B not a block_b multiple)
        (193, 64, 64),  # partial last block, exact smaller blocking
    ],
)
def test_pair_scatter_vs_ref(B, T, block_b):
    """Telemetry pair-statistic scatter kernel vs the float64 numpy oracle.

    Includes out-of-range types on *both* sides (-1, the wrapper's padding
    convention, and >= T, a masked/corrupt row): they must contribute
    nothing, exactly like the reference's explicit skip."""
    from repro.kernels.telemetry import pair_scatter

    rng = np.random.default_rng(B * 1000 + T)
    types = rng.integers(-1, T + 2, size=B).astype(np.int32)
    cbar = (rng.random((B, T)) * 2).astype(np.float32)
    vals = rng.normal(size=B).astype(np.float32)
    # debug=False: the >= T rows here exercise the kernel's silent-drop
    # semantics; the eager debug-mode bounds check (which treats >= T as a
    # misrouted index) has its own test in test_analysis.py
    pair, base = pair_scatter(jnp.asarray(types), jnp.asarray(cbar),
                              jnp.asarray(vals), block_b=block_b,
                              interpret=True, debug=False)
    pair_ref, base_ref = ref.pair_scatter_ref(types, cbar, vals)
    np.testing.assert_allclose(np.asarray(pair), pair_ref, atol=2e-5, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(base), base_ref, atol=2e-5, rtol=1e-5)


@pytest.mark.parametrize("B,T,K,block_b", [
    (40, 64, 2, 128),  # the estimator's stacked (residual, weight) pair
    (300, 32, 3, 128),  # partial last block with a stacked axis
    (64, 230, 1, 64),  # K=1 stacked differs from the squeezed 1-D contract
])
def test_pair_scatter_stacked_statistics(B, T, K, block_b):
    """The kernel scatters K stacked statistics in one pass ([K, B] vals ->
    [K, T, T] / [K, T]), matching the float64 oracle per statistic."""
    from repro.kernels.telemetry import pair_scatter

    rng = np.random.default_rng(B + T + K)
    types = rng.integers(-1, T, size=B).astype(np.int32)
    cbar = (rng.random((B, T)) * 2).astype(np.float32)
    vals = rng.normal(size=(K, B)).astype(np.float32)
    pair, base = pair_scatter(jnp.asarray(types), jnp.asarray(cbar),
                              jnp.asarray(vals), block_b=block_b, interpret=True)
    assert pair.shape == (K, T, T) and base.shape == (K, T)
    pair_ref, base_ref = ref.pair_scatter_ref(types, cbar, vals)
    np.testing.assert_allclose(np.asarray(pair), pair_ref, atol=2e-5, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(base), base_ref, atol=2e-5, rtol=1e-5)
    # stacking must agree with K independent single-statistic passes, at the
    # oracle's tolerance: the two programs may round the f32 accumulation of
    # one statistic differently (about one ulp)
    for k in range(K):
        p1, b1 = pair_scatter(jnp.asarray(types), jnp.asarray(cbar),
                              jnp.asarray(vals[k]), block_b=block_b, interpret=True)
        np.testing.assert_allclose(np.asarray(p1), np.asarray(pair[k]),
                                   atol=2e-5, rtol=1e-5)
        np.testing.assert_allclose(np.asarray(b1), np.asarray(base[k]),
                                   atol=2e-5, rtol=1e-5)


def test_pair_scatter_empty_batch_all_backends():
    """B = 0 returns zeros of the right shape on every backend, 1-D and
    stacked (the engine's empty-segment path hits this)."""
    from repro.kernels.telemetry import pair_scatter
    from repro.telemetry.estimator import make_scatter

    T = 16
    e_types = np.zeros(0, np.int32)
    e_cbar = np.zeros((0, T))
    pair, base = pair_scatter(jnp.asarray(e_types), jnp.asarray(e_cbar),
                              jnp.zeros((3, 0)), interpret=True)
    assert pair.shape == (3, T, T) and base.shape == (3, T)
    assert not np.asarray(pair).any() and not np.asarray(base).any()
    for backend in ("numpy", "jnp", "pallas"):
        p, b = make_scatter(backend)(e_types, e_cbar, np.zeros(0))
        assert p.shape == (T, T) and b.shape == (T,)
        assert not np.asarray(p).any() and not np.asarray(b).any()


def test_pair_scatter_matches_estimator_backends():
    """All three scatter backends implement one contract (estimator view),
    1-D and stacked. Tolerance reflects full-f32 accumulation: the jnp
    backend is jitted once and contracts with an explicit
    ``preferred_element_type`` (no bf16 downcast drift on any device)."""
    from repro.telemetry.estimator import make_scatter

    rng = np.random.default_rng(0)
    B, T = 40, 230
    types = rng.integers(0, T, size=B).astype(np.int32)
    cbar = (rng.random((B, T)) < 0.02).astype(np.float64) * rng.random((B, T))
    for vals in (rng.normal(size=B), rng.normal(size=(2, B))):
        want = make_scatter("numpy")(types, cbar, vals)
        for backend in ("jnp", "pallas"):
            got = make_scatter(backend)(types, cbar, vals)
            np.testing.assert_allclose(got[0], want[0], atol=1e-6)
            np.testing.assert_allclose(got[1], want[1], atol=1e-6)


def test_flash_attention_matches_model_layer():
    """Kernel path == the production jnp chunked_attention (same math)."""
    from repro.models.layers import chunked_attention

    B, S, Hkv, G, dh = 1, 64, 2, 2, 32
    ks = jax.random.split(jax.random.PRNGKey(4), 3)
    q = jax.random.normal(ks[0], (B, S, Hkv, G, dh))
    k = jax.random.normal(ks[1], (B, S, Hkv, dh))
    v = jax.random.normal(ks[2], (B, S, Hkv, dh))
    jnp_out = chunked_attention(q, k, v, causal=True, chunk=32)
    kq = q.reshape(B, S, Hkv * G, dh)
    kernel_out = ops.gqa_flash_attention(kq, k, v, causal=True, mode="interpret",
                                         block_q=32, block_k=32)
    np.testing.assert_allclose(
        np.asarray(jnp_out.reshape(B, S, -1, dh), np.float32),
        np.asarray(kernel_out, np.float32), atol=2e-5, rtol=2e-5)
