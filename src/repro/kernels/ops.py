"""Jit'd public wrappers for the model-zoo Pallas kernels (the ``ops.py``
layer). The consolidation kernels are called directly by their users.

These adapt model-layer tensor layouts to kernel layouts (GQA expansion,
head flattening) and select the execution mode: 'tpu' (real Mosaic lowering),
'interpret' (kernel body executed in Python on CPU -- how the CPU tests
validate correctness), or 'jnp' (the pure-jnp reference path the production
models default to off-TPU).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import ref
from .flash_attention import flash_attention
from .mamba_scan import mamba_scan
from .rwkv6_scan import rwkv6_scan


def _mode_kwargs(mode: str) -> dict:
    if mode == "tpu":
        return {"interpret": False}
    if mode == "interpret":
        return {"interpret": True}
    raise ValueError(f"mode must be tpu|interpret (got {mode!r}); use *_ref for jnp")


def gqa_flash_attention(
    q: jax.Array,  # [B, Sq, H, dh]
    k: jax.Array,  # [B, Skv, Hkv, dh]
    v: jax.Array,  # [B, Skv, Hkv, dh]
    *,
    causal: bool = True,
    q_offset: int = 0,
    mode: str = "interpret",
    block_q: int = 256,
    block_k: int = 256,
) -> jax.Array:
    """Model-layout wrapper: expands GQA kv heads and flattens (B, H)->N."""
    B, Sq, H, dh = q.shape
    Hkv = k.shape[2]
    G = H // Hkv
    kx = jnp.repeat(k, G, axis=2).transpose(0, 2, 1, 3).reshape(B * H, -1, dh)
    vx = jnp.repeat(v, G, axis=2).transpose(0, 2, 1, 3).reshape(B * H, -1, dh)
    qx = q.transpose(0, 2, 1, 3).reshape(B * H, Sq, dh)
    out = flash_attention(
        qx, kx, vx, causal=causal, q_offset=q_offset,
        block_q=block_q, block_k=block_k, **_mode_kwargs(mode),
    )
    return out.reshape(B, H, Sq, dh).transpose(0, 2, 1, 3)


def rwkv6_wkv(
    r: jax.Array,  # [B, S, H, dh]
    k: jax.Array,
    v: jax.Array,
    wlog: jax.Array,
    u: jax.Array,  # [H, dh]
    s0: jax.Array,  # [B, H, dh, dh]
    *,
    chunk: int = 32,
    mode: str = "interpret",
) -> tuple[jax.Array, jax.Array]:
    B, S, H, dh = r.shape
    fold = lambda x: x.transpose(0, 2, 1, 3).reshape(B * H, S, dh)
    y, sT = rwkv6_scan(
        fold(r), fold(k), fold(v), fold(wlog),
        jnp.broadcast_to(u[None], (B, H, dh)).reshape(B * H, dh),
        s0.reshape(B * H, dh, dh),
        chunk=chunk, **_mode_kwargs(mode),
    )
    return (y.reshape(B, H, S, dh).transpose(0, 2, 1, 3), sT.reshape(B, H, dh, dh))


def mamba_ssm_scan(
    da: jax.Array, dbu: jax.Array, c: jax.Array, h0: jax.Array,
    *, chunk: int = 64, eblock: int = 512, mode: str = "interpret",
) -> tuple[jax.Array, jax.Array]:
    return mamba_scan(da, dbu, c, h0, chunk=chunk, eblock=eblock, **_mode_kwargs(mode))
