"""The paper's greedy-placement scoring loop as a Pallas TPU kernel.

This is the consolidation scheduler's hot spot at fleet scale: for each of Q
queued workloads, score all m servers by tentatively placing the workload
(Fig 8 steps 2-3): cache_in_use' and Max(D_y)' under the additive model
(Eqn 3). The Python/jnp paths (core/binpack*.py) evaluate one candidate at a
time; this kernel batches Q x m candidate evaluations with the profiled
D-matrix tile [T, T] resident in VMEM (T=230 -> 212KB fp32) while the
candidate axis streams -- one D fetch per server for the whole queue.

grid = (m, Q / Qb); per step: a block of Qb <= BLOCK_Q candidates against
one server, ``[Qb, T] @ [T, T]`` on the MXU. Every block keeps its last two
dims either equal to the array's or (8, 128)-aligned, which the TPU lowering
requires: per-server operands carry a unit axis ([m, 1, T]) and the outputs
are [m, Q, 1] columns, transposed to cache_after [Q, m], maxd_after [Q, m]
outside -- argmin over the feasible set happens there too (cheap [Q, m]
reduction).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _score_kernel(counts_ref, d_ref, diag_ref, rsfs_ref, budget_ref, wtype_ref,
                  cache_ref, maxd_ref):
    counts = counts_ref[0]  # [1, T]
    D = d_ref[0]  # [T, T]
    diag = diag_ref[0]  # [1, T]
    rs = rsfs_ref[0, 0:1]  # [1, T]
    fs_res = rsfs_ref[0, 1:2]  # [1, T] fs * resident mask (0 where non-competing)
    budget = budget_ref[0]  # [1, 1]
    wt = wtype_ref[...]  # [Qb, 1] candidate types (-1 = padding, selects nothing)

    Qb, T = wt.shape[0], D.shape[0]
    onehot = (jax.lax.broadcasted_iota(jnp.int32, (Qb, T), 1) == wt).astype(jnp.float32)
    c = counts + onehot  # [Qb, T]

    comp = (jnp.sum(c * rs, axis=1, keepdims=True)
            + jnp.sum(c * fs_res, axis=1, keepdims=True))  # [Qb, 1]
    cache_ref[0] = comp / budget

    # c @ D in full f32: Mosaic's default f32 dot is not, and would move
    # decisions off the float64 reference (see kernels/telemetry.py)
    col = jax.lax.dot_general(c, D, (((1,), (0,)), ((), ())),
                              precision=jax.lax.Precision.HIGHEST,
                              preferred_element_type=jnp.float32)  # [Qb, T]
    d_pred = jnp.clip(col - diag, 0.0, 1.0)
    present = c > 0
    maxd_ref[0] = jnp.max(jnp.where(present, d_pred, -jnp.inf), axis=1, keepdims=True)


#: candidates per kernel program: a multiple of 8 (the sublane tile), large
#: enough to fill the MXU's rows, small enough that a block's [BLOCK_Q, T]
#: temporaries stay a fraction of VMEM
BLOCK_Q = 256


@functools.partial(jax.jit, static_argnames=("interpret",))
def consolidation_scores(
    counts: jax.Array,  # [m, T] resident workload counts per server
    D: jax.Array,  # [m, T, T] profiled pairwise degradations
    rs: jax.Array,  # [T] request sizes (bytes)
    fs_resident: jax.Array,  # [m, T] fs * (fs <= llc) per server
    llc_budget: jax.Array,  # [m] alpha * CacheSize
    wtypes: jax.Array,  # [Q] candidate grid types (int32)
    *,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    m, T = counts.shape
    Q = wtypes.shape[0]
    Qb = min(Q, BLOCK_Q)
    Qp = -(-Q // Qb) * Qb
    f32 = lambda x: x.astype(jnp.float32)
    diag = jnp.diagonal(D, axis1=1, axis2=2)  # [m, T]
    rsfs = jnp.stack([jnp.broadcast_to(rs, (m, T)), fs_resident], axis=1)  # [m, 2, T]
    wt = jnp.pad(wtypes.astype(jnp.int32), (0, Qp - Q),
                 constant_values=-1).reshape(Qp, 1)

    cache, maxd = pl.pallas_call(
        _score_kernel,
        grid=(m, Qp // Qb),
        in_specs=[
            pl.BlockSpec((1, 1, T), lambda s, q: (s, 0, 0)),
            pl.BlockSpec((1, T, T), lambda s, q: (s, 0, 0)),
            pl.BlockSpec((1, 1, T), lambda s, q: (s, 0, 0)),
            pl.BlockSpec((1, 2, T), lambda s, q: (s, 0, 0)),
            pl.BlockSpec((1, 1, 1), lambda s, q: (s, 0, 0)),
            pl.BlockSpec((Qb, 1), lambda s, q: (q, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, Qb, 1), lambda s, q: (s, q, 0)),
            pl.BlockSpec((1, Qb, 1), lambda s, q: (s, q, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((m, Qp, 1), jnp.float32),
            jax.ShapeDtypeStruct((m, Qp, 1), jnp.float32),
        ],
        interpret=interpret,
    )(f32(counts).reshape(m, 1, T), f32(D), f32(diag).reshape(m, 1, T),
      f32(rsfs), f32(llc_budget).reshape(m, 1, 1), wt)
    return cache[:, :Q, 0].T, maxd[:, :Q, 0].T
