"""Batched pair-statistic scatter-accumulation as a Pallas TPU kernel.

The telemetry estimator (``repro.telemetry.estimator``) reduces every batch
of completion observations to per-pair sufficient statistics: for a batch of
B observations -- target grid type ``t_b``, co-resident exposure row
``cbar_b`` [T], and K scalar statistics ``v_b^k`` per observation (the
residual numerator and the exposure weight of one LMS step, stacked) -- it
needs, for every statistic k,

  pair[k, u, t] = sum_b cbar_b[u] * v_b^k * 1{t_b == t}        [K, T, T]
  base[k, t]    = sum_b            v_b^k * 1{t_b == t}         [K, T]

i.e. a scatter over the *target-type column* with the co-resident row as the
update. At fleet scale this runs once per trace segment over thousands of
observations with T = 230, so the batch is streamed through the MXU as a
[T, Bb] x [Bb, T] contraction per (block, statistic) instead of a
python-level scatter: the one-hot column selector turns the scatter into a
matmul, and the [K, T, T] output block stays resident in VMEM across the
whole batch (the grid walks the batch axis only, revisiting the same output
tile). Stacking the K statistics amortizes the batch stream: the one-hot
selector is built once per block and every statistic reuses it -- the
estimator's residual numerator and exposure weight ride one pass where they
used to take two kernel launches.

Validated against the float64 numpy reference ``kernels.ref.pair_scatter_ref``
in tests/test_kernels.py. Out-of-range types (e.g. the -1 padding the wrapper
adds to fill the last block, or rows a validity mask voided upstream) select
no column and contribute nothing, exactly like the reference's explicit skip.

Index-space contract: the scatter is agnostic to what its row indices *mean*.
The estimator bank feeds it per-server splits, and since the fleet-health
subsystem (``repro.fleet``) those indices are **pool ids** -- several servers
remapped onto one shared estimator row (``EstimatorBank.update_device(...,
row_map=...)``) -- so a pooled row's statistics accumulate every member's
observations in the same pass. Rows remapped to -1 (evicted servers) ride the
same out-of-range drop as padding. Indices *past* the table (>= T) are also
dropped by the kernel, but no well-formed caller produces them -- in debug
mode (``debug=True``, defaulting to ``interpret``) the wrapper pulls the
eager types to the host and raises on any type >= T before launch.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl


def _pair_scatter_kernel(types_ref, cbar_ref, vals_ref, pair_ref, base_ref):
    b = pl.program_id(0)

    types = types_ref[:, 0]  # [Bb] i32
    vals = vals_ref[...].astype(jnp.float32)  # [Bb, K]
    cbar = cbar_ref[...].astype(jnp.float32)  # [Bb, T]
    Bb, T = cbar.shape
    K = vals.shape[1]

    # one-hot target-type selector; padding types (< 0 or >= T) select nothing
    onehot = (
        jax.lax.broadcasted_iota(jnp.int32, (Bb, T), 1) == types[:, None]
    ).astype(jnp.float32)

    @pl.when(b == 0)
    def _init():
        pair_ref[...] = jnp.zeros_like(pair_ref)
        base_ref[...] = jnp.zeros_like(base_ref)

    # full f32 contractions: Mosaic's default f32 dot is not (it missed the
    # float64 reference by 0.028 at B = 256, T = 230 on a v5e)
    hi = jax.lax.Precision.HIGHEST
    # base[k, t] += sum_b vals[b, k] 1{t_b = t}: one [K, Bb] x [Bb, T] MXU pass
    base_ref[...] += jax.lax.dot_general(
        vals, onehot, (((0,), (0,)), ((), ())), precision=hi,
        preferred_element_type=jnp.float32)
    # cbar^T @ (onehot * v_k): contract the batch axis on the MXU per statistic
    # -> K [T, T] column scatters sharing one selector build (K is static and
    # small -- 1 or 2 in the estimator -- so the unrolled loop costs nothing)
    for k in range(K):
        pair_ref[k] += jax.lax.dot_general(
            cbar, onehot * vals[:, k][:, None], (((0,), (0,)), ((), ())),
            precision=hi, preferred_element_type=jnp.float32)


def pair_scatter(
    types: jax.Array,  # i32[B] target grid type per observation
    cbar: jax.Array,  # f32[B, T] co-resident exposure rows
    vals: jax.Array,  # f32[B] or f32[K, B]: K stacked statistics per observation
    *,
    block_b: int = 128,
    interpret: bool = False,
    debug: "bool | None" = None,
) -> tuple[jax.Array, jax.Array]:
    """Sufficient statistics for one observation batch.

    ``vals`` of shape [B] returns ``(pair [T, T], base [T])`` (the original
    single-statistic contract); [K, B] returns ``(pair [K, T, T], base
    [K, T])`` with all K statistics accumulated in one batch stream.

    ``debug`` (defaults to ``interpret``) enforces the index-space contract
    before launch: *negative* types are part of the contract -- padding rows
    and evicted pool ids deliberately select no column -- but a type ``>= T``
    is never produced by a well-formed caller; it means a pool id or grid
    type was misrouted past the table, and the silent-drop semantics would
    swallow that observation. The check pulls ``types`` to the host, so it
    only runs eagerly (skipped under an enclosing trace) and only when
    ``debug`` is on.
    """
    if debug is None:
        debug = interpret
    if debug and not isinstance(types, jax.core.Tracer):
        T = cbar.shape[1]
        t = np.asarray(types)
        if t.size and int(t.max(initial=-1)) >= T:
            bad = int(np.argmax(t >= T))
            raise ValueError(
                f"pair_scatter index-space contract violated: types[{bad}] = "
                f"{int(t[bad])} >= T = {T}. Negative types (padding / evicted "
                f"pool rows) are dropped by design, but an index past the "
                f"table means a misrouted pool id or grid type -- the scatter "
                f"would silently discard that observation.")
    return _pair_scatter_impl(
        types, cbar, vals, block_b=block_b, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("block_b", "interpret"))
def _pair_scatter_impl(
    types: jax.Array,
    cbar: jax.Array,
    vals: jax.Array,
    *,
    block_b: int = 128,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    B, T = cbar.shape
    squeeze = vals.ndim == 1
    vals2 = vals[None, :] if squeeze else vals  # [K, B]
    K = vals2.shape[0]
    if B == 0:  # match the jnp/numpy backends of the contract
        pair = jnp.zeros((K, T, T), jnp.float32)
        base = jnp.zeros((K, T), jnp.float32)
        return (pair[0], base[0]) if squeeze else (pair, base)
    vals_bk = vals2.T.astype(jnp.float32)  # [B, K] batch-major for blocking
    Bb = min(block_b, B)
    pad = (-B) % Bb
    if pad:
        # padded rows carry type -1: the one-hot selector drops them
        types = jnp.concatenate([types, jnp.full((pad,), -1, types.dtype)])
        cbar = jnp.concatenate([cbar, jnp.zeros((pad, T), cbar.dtype)])
        vals_bk = jnp.concatenate([vals_bk, jnp.zeros((pad, K), vals_bk.dtype)])
    nb = (B + pad) // Bb

    pair, base = pl.pallas_call(
        _pair_scatter_kernel,
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((Bb, 1), lambda i: (i, 0)),
            pl.BlockSpec((Bb, T), lambda i: (i, 0)),
            pl.BlockSpec((Bb, K), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((K, T, T), lambda i: (0, 0, 0)),
            pl.BlockSpec((K, T), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((K, T, T), jnp.float32),
            jax.ShapeDtypeStruct((K, T), jnp.float32),
        ],
        interpret=interpret,
    )(types.reshape(-1, 1).astype(jnp.int32),
      cbar.astype(jnp.float32),
      vals_bk)
    return (pair[0], base[0]) if squeeze else (pair, base)
