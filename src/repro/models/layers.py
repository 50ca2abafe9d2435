"""Shared neural layers for the model zoo: norms, RoPE, GQA attention
(flash-style chunked softmax in pure jnp), dense MLP, and MoE.

Everything here is *functional*: ``*_infos(cfg)`` declares parameters
(:class:`repro.models.params.ParamInfo` pytrees), ``*_apply`` consumes the
materialized (or abstract) arrays. Activation shardings are injected through
the :func:`activation_sharding` context so the same code runs unsharded on
one CPU device (smoke tests) and GSPMD-sharded on the production mesh.
"""
from __future__ import annotations

import contextlib
import math
import os
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec

from .params import ParamInfo


def _shard_map(body, *, mesh, in_specs, out_specs, axis_names):
    """``jax.shard_map`` manual over ``axis_names`` (auto over the rest),
    with replication checking off."""
    return jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, axis_names=axis_names,
                         check_vma=False)

# --- activation-sharding context ------------------------------------------------

_CTX: dict = {"mesh": None, "rules": None}


@contextlib.contextmanager
def activation_sharding(mesh, rules: dict):
    """Install (mesh, logical-axis rules) for `shard()` constraints while tracing."""
    prev = dict(_CTX)
    _CTX["mesh"], _CTX["rules"] = mesh, rules
    try:
        yield
    finally:
        _CTX.update(prev)


def _axis_product(mesh, axes) -> int:
    if axes is None:
        return 1
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    if isinstance(axes, str):
        return sizes.get(axes, 1)
    n = 1
    for a in axes:
        n *= sizes.get(a, 1)
    return n


def shard(x: jax.Array, *axes: str | None) -> jax.Array:
    """Constrain activation ``x`` to the sharding implied by logical ``axes``.

    No-op when no mesh is installed (single-device tests) or when the rank
    does not match; mesh axes that do not divide the dimension are dropped
    (pjit divisibility), leaving GSPMD to choose for that dim.
    """
    mesh, rules = _CTX["mesh"], _CTX["rules"]
    if mesh is None or rules is None or len(axes) != x.ndim:
        return x
    resolved = []
    for dim, a in zip(x.shape, axes):
        mesh_axes = rules.get(a) if a is not None else None
        n = _axis_product(mesh, mesh_axes)
        resolved.append(mesh_axes if (n == 1 or dim % n == 0) and n > 1 else None)
    spec = PartitionSpec(*resolved)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def mesh_info() -> tuple:
    """(mesh, rules) currently installed, or (None, None)."""
    return _CTX["mesh"], _CTX["rules"]


# --- vocab padding ------------------------------------------------------------------
# Pad vocab to a multiple of 256 so the vocab dim always divides TP (the
# Megatron trick). Padded logit columns are masked to -1e30 before any
# softmax/argmax, so they are semantically inert.

def padded_vocab(v: int) -> int:
    return -(-v // 256) * 256


def mask_padded_logits(logits: jax.Array, true_vocab: int) -> jax.Array:
    if logits.shape[-1] == true_vocab:
        return logits
    col = jax.lax.broadcasted_iota(jnp.int32, logits.shape[-1:], 0)
    neg = jnp.asarray(-1e30, logits.dtype)
    return jnp.where(col < true_vocab, logits, neg)


def sharded_embed(table: jax.Array, tokens: jax.Array, cfg) -> jax.Array:
    """Embedding lookup with a vocab-sharded table, manual over the mesh.

    GSPMD replicates the gather *transpose* (a [V, D] f32 scatter-add per
    device -- 22GiB at kimi scale), so the lookup runs under shard_map: each
    shard gathers from its local vocab rows (ids outside the range contribute
    zeros) and one psum over 'model' assembles the embeddings; the backward
    is then a local scatter-add into the local rows only.
    """
    mesh, _ = mesh_info()
    dt = cfg.compute_dtype
    if mesh is None or _axis_product(mesh, "model") <= 1 or getattr(cfg, "layout", "tp") != "tp":
        return table.astype(dt)[tokens]
    tp = _axis_product(mesh, "model")
    Vp = table.shape[0]
    if Vp % tp != 0:
        return table.astype(dt)[tokens]
    V_loc = Vp // tp
    data_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    dp = _axis_product(mesh, data_axes)
    B = tokens.shape[0]
    batch_spec = data_axes if (B % max(dp, 1) == 0 and dp > 1) else None

    def body(tbl, ids):
        off = jax.lax.axis_index("model") * V_loc
        local = ids - off
        ok = (local >= 0) & (local < V_loc)
        x = tbl[jnp.clip(local, 0, V_loc - 1)].astype(jnp.float32)
        x = jnp.where(ok[..., None], x, 0.0)
        return jax.lax.psum(x, "model")

    # table in_spec: vocab rows over 'model'; its dmodel dim may carry the
    # FSDP data axes -- gather it at the boundary (bf16, cheap vs the grads).
    out = _shard_map(
        body,
        mesh=mesh,
        in_specs=(PartitionSpec("model", None), PartitionSpec(batch_spec, None)),
        out_specs=PartitionSpec(batch_spec, None, None),
        axis_names={"model", *data_axes},
    )(table, tokens)
    return out.astype(dt)


# --- norms -----------------------------------------------------------------------

def norm_infos(cfg, name: str = "norm") -> dict:
    d = {"scale": ParamInfo((cfg.d_model,), ("dmodel",), "ones")}
    if cfg.norm == "layernorm":
        d["bias"] = ParamInfo((cfg.d_model,), ("dmodel",), "zeros")
    return d


def norm_apply(p: dict, x: jax.Array, cfg) -> jax.Array:
    xf = x.astype(jnp.float32)
    if cfg.norm == "layernorm":
        mu = xf.mean(-1, keepdims=True)
        var = ((xf - mu) ** 2).mean(-1, keepdims=True)
        y = (xf - mu) * jax.lax.rsqrt(var + 1e-5) * p["scale"] + p["bias"]
    else:  # rmsnorm
        var = (xf**2).mean(-1, keepdims=True)
        y = xf * jax.lax.rsqrt(var + 1e-6) * p["scale"]
    return y.astype(x.dtype)


# --- rotary position embeddings ----------------------------------------------------

def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """Apply RoPE. x: [..., S, H, dh], positions: [..., S] (broadcastable)."""
    dh = x.shape[-1]
    half = dh // 2
    freqs = jnp.exp(-math.log(theta) * jnp.arange(half, dtype=jnp.float32) / half)
    angles = positions[..., :, None].astype(jnp.float32) * freqs[None, :]  # [..., S, half]
    cos = jnp.cos(angles)[..., :, None, :]  # [..., S, 1, half]
    sin = jnp.sin(angles)[..., :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    xf1, xf2 = x1.astype(jnp.float32), x2.astype(jnp.float32)
    return jnp.concatenate([xf1 * cos - xf2 * sin, xf2 * cos + xf1 * sin], axis=-1).astype(x.dtype)


def quantize_kv(x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Symmetric int8 quantization per (token, head): x [B,S,H,dh] ->
    (int8 [B,S,H,dh], bf16 scales [B,S,H]). Halves the KV-cache bytes and,
    more importantly for decode, halves the per-step HBM read volume."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1)
    scale = jnp.maximum(amax / 127.0, 1e-8)
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale[..., None]), -127, 127)
    return q.astype(jnp.int8), scale.astype(jnp.bfloat16)


# --- GQA attention ------------------------------------------------------------------

def attention_infos(cfg, cross: bool = False) -> dict:
    H, Hkv, dh, D = cfg.n_heads, cfg.n_kv_heads, cfg.d_head, cfg.d_model
    d = {
        "wq": ParamInfo((D, H, dh), ("dmodel", "heads", None)),
        "wk": ParamInfo((D, Hkv, dh), ("dmodel", "kv_heads", None)),
        "wv": ParamInfo((D, Hkv, dh), ("dmodel", "kv_heads", None)),
        "wo": ParamInfo((H, dh, D), ("heads", None, "dmodel")),
    }
    if cfg.qkv_bias:
        d["bq"] = ParamInfo((H, dh), ("heads", None), "zeros")
        d["bk"] = ParamInfo((Hkv, dh), ("kv_heads", None), "zeros")
        d["bv"] = ParamInfo((Hkv, dh), ("kv_heads", None), "zeros")
    return d


def _qkv(p: dict, x: jax.Array, cfg, positions, rope_on: bool):
    """Project to grouped q [B,S,Hkv,G,dh] and k,v [B,S,Hkv,dh]."""
    H, Hkv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    G = H // Hkv
    dt = cfg.compute_dtype
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(dt))
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"].astype(dt))
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"].astype(dt))
    if cfg.qkv_bias:
        q = q + p["bq"].astype(dt)
        k = k + p["bk"].astype(dt)
        v = v + p["bv"].astype(dt)
    if rope_on:
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)
    q = q.reshape(*q.shape[:2], Hkv, G, dh)
    return q, k, v


def chunked_attention(
    q: jax.Array,  # [B, Sq, Hkv, G, dh]
    k: jax.Array,  # [B, Skv, Hkv, dh]
    v: jax.Array,  # [B, Skv, Hkv, dh]
    *,
    causal: bool,
    q_offset: jax.Array | int = 0,  # absolute position of q[0] (decode/chunk offset)
    kv_offset: jax.Array | int = 0,  # absolute position of k[0] (windowed cache slice)
    kv_valid: jax.Array | int | None = None,  # #valid kv entries, in absolute positions
    chunk: int = 1024,
    window: int = 0,  # sliding window size, 0 = unlimited
) -> jax.Array:
    """Numerically-stable softmax attention, chunked over the query axis.

    This is the pure-jnp flash-attention reference: it never materializes a
    full [Sq, Skv] score tensor larger than [chunk, Skv], which keeps the
    32k-prefill memory footprint linear. The Pallas kernel in
    repro/kernels/flash_attention.py is the TPU-optimized equivalent and is
    validated against this function.
    """
    B, Sq, Hkv, G, dh = q.shape
    Skv = k.shape[1]
    scale = 1.0 / math.sqrt(dh)
    kv_pos = kv_offset + jnp.arange(Skv)

    def attend(q_chunk: jax.Array, q_pos: jax.Array) -> jax.Array:
        # q_chunk: [B, cq, Hkv, G, dh]; q_pos: [cq] absolute positions
        s = jnp.einsum("bqhgk,bthk->bhgqt", q_chunk, k).astype(jnp.float32) * scale
        mask = jnp.ones((q_pos.shape[0], Skv), bool)
        if causal:
            mask &= q_pos[:, None] >= kv_pos[None, :]
        if window:
            mask &= kv_pos[None, :] > q_pos[:, None] - window
        if kv_valid is not None:
            mask &= kv_pos[None, :] < kv_valid
        s = jnp.where(mask[None, None, None], s, -1e30)
        w = jax.nn.softmax(s, axis=-1).astype(q.dtype)
        return jnp.einsum("bhgqt,bthk->bqhgk", w, v)

    if Sq <= chunk:
        return attend(q, q_offset + jnp.arange(Sq))

    n = -(-Sq // chunk)
    pad = n * chunk - Sq
    if pad:  # pad queries to a whole number of chunks; extra rows are dropped
        q = jnp.concatenate([q, jnp.zeros((B, pad, Hkv, G, dh), q.dtype)], axis=1)
    qs = q.reshape(B, n, chunk, Hkv, G, dh).transpose(1, 0, 2, 3, 4, 5)
    offs = q_offset + jnp.arange(n) * chunk

    def body(_, xs):
        qc, off = xs
        return None, attend(qc, off + jnp.arange(chunk))

    # flash-style backward: recompute each chunk's scores/softmax in the
    # backward pass instead of saving [chunk, Skv] f32 weights per chunk.
    _, out = jax.lax.scan(jax.checkpoint(body), None, (qs, offs))
    out = out.transpose(1, 0, 2, 3, 4, 5).reshape(B, n * chunk, Hkv, G, dh)
    return out[:, :Sq]


def _seq_sharded_attention(q, k, v, *, causal, chunk, window, mesh):
    """shard_map attention for head counts that do not divide TP: queries are
    sequence-sharded over 'model', K/V replicated across it; each shard runs
    the chunked online-softmax locally with its absolute q offset. No
    collectives inside -- the surrounding projections reshard."""
    S = q.shape[1]
    tp = dict(zip(mesh.axis_names, mesh.devices.shape)).get("model", 1)
    if tp == 1 or S % tp != 0:
        return chunked_attention(q, k, v, causal=causal, chunk=chunk, window=window)
    local = S // tp
    dt = q.dtype

    def body(ql, kl, vl):
        off = jax.lax.axis_index("model") * local
        return chunked_attention(
            ql, kl.astype(dt), vl.astype(dt),
            causal=causal, q_offset=off, chunk=chunk, window=window,
        )

    # k/v cross the boundary in f32 (replicated-input cotangents lower to
    # copy-combiner all-reduces that XLA:CPU aborts on in bf16; see MoE note).
    return _shard_map(
        body,
        mesh=mesh,
        in_specs=(
            PartitionSpec(None, "model", None, None, None),
            PartitionSpec(None, None, None, None),
            PartitionSpec(None, None, None, None),
        ),
        out_specs=PartitionSpec(None, "model", None, None, None),
        axis_names={"model"},
    )(q, k.astype(jnp.float32), v.astype(jnp.float32))


def attention_apply(
    p: dict,
    x: jax.Array,  # [B, S, D]
    cfg,
    *,
    positions: jax.Array,  # [S] absolute positions of x
    cache: dict | None = None,  # {'k': [B,T,Hkv,dh], 'v': ..., 'len': scalar}
    causal: bool = True,
    rope_on: bool = True,
    window: int = 0,
) -> tuple[jax.Array, dict | None]:
    """Self-attention with optional KV cache. Returns (out [B,S,D], new_cache)."""
    dt = cfg.compute_dtype
    q, k, v = _qkv(p, x, cfg, positions, rope_on)
    q = shard(q, "batch", None, "act_heads", None, None)
    k = shard(k, "batch", None, "act_heads", None)
    v = shard(v, "batch", None, "act_heads", None)

    if cache is None:
        mesh, _ = mesh_info()
        tp = _axis_product(mesh, "model") if mesh is not None else 1
        if cfg.attn_shard == "seq" and mesh is not None and x.shape[1] > 1:
            out = _seq_sharded_attention(
                q, k, v, causal=causal, chunk=cfg.attn_chunk, window=window, mesh=mesh
            )
        else:
            if tp > 1 and k.shape[2] % tp != 0 and not os.environ.get("REPRO_DISABLE_KVEXP"):
                # GQA kv heads do not divide TP: expand kv to full query heads
                # so the head dim shards (q regrouped to G=1). Memory cost is
                # G x on k/v activations, /tp sharded -- net win vs replicated
                # attention scores.
                G = q.shape[3]
                k = shard(jnp.repeat(k, G, axis=2), "batch", None, "act_heads", None)
                v = shard(jnp.repeat(v, G, axis=2), "batch", None, "act_heads", None)
                B, S, Hkv, G_, dh = q.shape
                q = q.reshape(B, S, Hkv * G_, 1, dh)
                q = shard(q, "batch", None, "act_heads", None, None)
            out = chunked_attention(
                q, k, v, causal=causal, chunk=cfg.attn_chunk, window=window
            )
        new_cache = None
    else:
        idx = cache["len"]
        S = x.shape[1]
        quant = cache["k"].dtype == jnp.int8

        def write(buf, val, rank4=True):
            return jax.lax.dynamic_update_slice(
                buf, val.astype(buf.dtype), (0, idx, 0, 0) if rank4 else (0, idx, 0))

        # pin the updated cache to its canonical sharding: without the
        # constraint GSPMD ping-pongs between time-sharded and head-sharded
        # layouts around the DUS ("involuntary full rematerialization").
        if getattr(cfg, "kv_cache_time_sharded", False):
            pin = lambda a: shard(a, "batch", "cache_time", None, None)
            pin3 = lambda a: shard(a, "batch", "cache_time", None)
        else:
            pin = lambda a: shard(a, "batch", None, "kv_heads", None)
            pin3 = lambda a: shard(a, "batch", None, "kv_heads")
        if quant:
            kq, ks = quantize_kv(k)
            vq, vs = quantize_kv(v)
            ck, cv = pin(write(cache["k"], kq)), pin(write(cache["v"], vq))
            cks = pin3(write(cache["k_scale"], ks, rank4=False))
            cvs = pin3(write(cache["v_scale"], vs, rank4=False))
        else:
            ck, cv = pin(write(cache["k"], k)), pin(write(cache["v"], v))
            cks = cvs = None

        rk, rv, rks, rvs, kv_off = ck, cv, cks, cvs, 0
        if window and ck.shape[1] > window + S:
            # sliding window: read only the last `window+S` cache entries --
            # at 500k context this cuts per-step attention reads by T/window.
            kv_off = jnp.maximum(idx + S - (window + S), 0)
            sl = lambda a, r=1: jax.lax.dynamic_slice_in_dim(a, kv_off, window + S, axis=r)
            rk, rv = sl(rk), sl(rv)
            if quant:
                rks, rvs = sl(rks), sl(rvs)
        if quant:
            rk = rk.astype(dt) * rks[..., None].astype(dt)
            rv = rv.astype(dt) * rvs[..., None].astype(dt)
        out = chunked_attention(
            q, rk.astype(dt), rv.astype(dt),
            causal=causal, q_offset=idx, kv_offset=kv_off, kv_valid=idx + S,
            chunk=cfg.attn_chunk, window=window,
        )
        new_cache = {"k": ck, "v": cv, "len": idx + S}
        if quant:
            new_cache.update(k_scale=cks, v_scale=cvs)

    B, S = x.shape[:2]
    out = out.reshape(B, S, cfg.n_heads, cfg.d_head)
    y = jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(dt))
    return shard(y, "batch", None, None), new_cache


def cross_attention_apply(p: dict, x: jax.Array, cfg, enc_kv: tuple[jax.Array, jax.Array]):
    """Cross-attention against precomputed encoder K/V (whisper decoder)."""
    dt = cfg.compute_dtype
    H, Hkv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(dt))
    if cfg.qkv_bias:
        q = q + p["bq"].astype(dt)
    q = q.reshape(*q.shape[:2], Hkv, H // Hkv, dh)
    k, v = enc_kv
    out = chunked_attention(q, k.astype(dt), v.astype(dt), causal=False, chunk=cfg.attn_chunk)
    B, S = x.shape[:2]
    out = out.reshape(B, S, H, dh)
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(dt))


def encoder_kv(p: dict, enc_out: jax.Array, cfg) -> tuple[jax.Array, jax.Array]:
    """Precompute cross-attention K/V from encoder output (done at prefill)."""
    dt = cfg.compute_dtype
    k = jnp.einsum("bsd,dhk->bshk", enc_out, p["wk"].astype(dt))
    v = jnp.einsum("bsd,dhk->bshk", enc_out, p["wv"].astype(dt))
    if cfg.qkv_bias:
        k = k + p["bk"].astype(dt)
        v = v + p["bv"].astype(dt)
    return k, v


# --- dense MLP ------------------------------------------------------------------------

def mlp_infos(cfg, d_ff: int | None = None) -> dict:
    D = cfg.d_model
    F = d_ff or cfg.d_ff
    if cfg.act == "swiglu":
        return {
            "wi": ParamInfo((D, 2, F), ("dmodel", None, "mlp")),  # gate & up fused
            "wo": ParamInfo((F, D), ("mlp", "dmodel")),
        }
    return {
        "wi": ParamInfo((D, F), ("dmodel", "mlp")),
        "bi": ParamInfo((F,), ("mlp",), "zeros"),
        "wo": ParamInfo((F, D), ("mlp", "dmodel")),
        "bo": ParamInfo((D,), ("dmodel",), "zeros"),
    }


def mlp_apply(p: dict, x: jax.Array, cfg) -> jax.Array:
    dt = cfg.compute_dtype
    if cfg.act == "swiglu":
        h = jnp.einsum("bsd,dcf->bscf", x, p["wi"].astype(dt))
        h = shard(h, "batch", None, None, "act_heads")
        h = jax.nn.silu(h[..., 0, :]) * h[..., 1, :]
    else:
        h = jnp.einsum("bsd,df->bsf", x, p["wi"].astype(dt)) + p["bi"].astype(dt)
        h = shard(h, "batch", None, "act_heads")
        h = jax.nn.gelu(h)
    y = jnp.einsum("bsf,fd->bsd", h, p["wo"].astype(dt))
    if cfg.act != "swiglu":
        y = y + p["bo"].astype(dt)
    return shard(y, "batch", None, None)


# --- Mixture of Experts -------------------------------------------------------------------

def moe_infos(cfg) -> dict:
    D, E, F = cfg.d_model, cfg.moe_experts, cfg.moe_dff
    return {
        "router": ParamInfo((D, E), ("dmodel", "expert"), "small"),
        "wi": ParamInfo((E, D, 2, F), ("expert", "expert_dmodel", None, None)),
        "wo": ParamInfo((E, F, D), ("expert", None, "expert_dmodel")),
    }


def moe_capacity(cfg, tokens_per_group: int) -> int:
    c = math.ceil(tokens_per_group * cfg.moe_topk * cfg.moe_capacity_factor / cfg.moe_experts)
    return max(4, int(c))


def _dispatch_tokens(tokens: jax.Array, expert_idx: jax.Array, gate_w: jax.Array, E: int, C: int):
    """Sort-based dispatch of one token group.

    tokens: [N, D]; expert_idx/gate_w: [N, K]. Expert ids >= E (sentinel) or
    beyond capacity are dropped. Returns
      buf   [E, C, D]  -- tokens gathered per expert (capacity-truncated)
      meta  (src [E, C] int32 token index or -1, w [E, C] gate weight)
    """
    N, K = expert_idx.shape
    flat_e = jnp.minimum(expert_idx.reshape(-1), E)  # [N*K]; E = dropped bucket
    flat_w = gate_w.reshape(-1)
    flat_tok = jnp.repeat(jnp.arange(N), K)

    order = jnp.argsort(flat_e, stable=True)
    se, sw, st = flat_e[order], flat_w[order], flat_tok[order]
    # position within the expert segment
    counts = jnp.bincount(se, length=E + 1)
    seg_start = jnp.concatenate([jnp.zeros(1, counts.dtype), jnp.cumsum(counts)[:-1]])
    pos = jnp.arange(N * K) - seg_start[se]
    keep = (pos < C) & (se < E)
    slot = jnp.where(keep, se * C + pos, E * C)  # overflow slot dropped

    src = jnp.full((E * C + 1,), -1, jnp.int32).at[slot].set(st.astype(jnp.int32))[:-1]
    w = jnp.zeros((E * C + 1,), jnp.float32).at[slot].set(sw.astype(jnp.float32))[:-1]
    src = src.reshape(E, C)
    w = w.reshape(E, C)
    buf = jnp.where(src[..., None] >= 0, tokens[jnp.maximum(src, 0)], 0.0)
    return buf, (src, w)


def _moe_expert_parallel(p: dict, x: jax.Array, cfg, group: str, mesh) -> jax.Array:
    """Expert-parallel MoE via shard_map (the production path).

    FULLY manual over every mesh axis: the sort/scatter dispatch is data-
    dependent, and GSPMD left to its own devices replicates the batch through
    it (measured 17GiB/device buffers at kimi scale). Manual data-axis
    sharding keeps everything local: each shard holds its batch rows and its
    E/tp experts, dispatches into a LOCAL capacity buffer [E/tp, C, D], runs
    its experts, and one psum over 'model' combines the partial outputs (the
    classic EP all-reduce).
    """
    E, K = cfg.moe_experts, cfg.moe_topk
    tp = _axis_product(mesh, "model")
    E_loc = E // tp
    dt = cfg.compute_dtype
    B, S, D = x.shape
    C = moe_capacity(cfg, S if group == "seq" else B * S)
    data_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    dp = _axis_product(mesh, data_axes)
    batch_spec = data_axes if (B % max(dp, 1) == 0 and dp > 1) else None

    fsdp = (bool(getattr(cfg, "fsdp", False)) and getattr(cfg, "expert_fsdp", True)
            and len(data_axes) > 0)

    def body(xl, router, wi, wo):
        xl = xl.astype(dt)
        if fsdp:
            # FSDP un-shard of the expert weights, explicit and in bf16 --
            # leaving it to the shard_map boundary materializes f32 copies
            # of weight + gradient (measured ~18GiB at kimi scale).
            wi = jax.lax.all_gather(wi.astype(dt), data_axes, axis=1, tiled=True)
            wo = jax.lax.all_gather(wo.astype(dt), data_axes, axis=2, tiled=True)
        logits = jnp.einsum("bsd,de->bse", xl, router.astype(jnp.float32))
        gates = jax.nn.softmax(logits, axis=-1)
        gate_w, expert_idx = jax.lax.top_k(gates, K)
        gate_w = gate_w / jnp.maximum(gate_w.sum(-1, keepdims=True), 1e-9)
        e0 = jax.lax.axis_index("model") * E_loc
        local_idx = jnp.where(
            (expert_idx >= e0) & (expert_idx < e0 + E_loc), expert_idx - e0, E_loc
        )

        def ffn(buf):
            h = jnp.einsum("...ecd,edgf->...ecgf", buf, wi.astype(dt))
            h = jax.nn.silu(h[..., 0, :]) * h[..., 1, :]
            return jnp.einsum("...ecf,efd->...ecd", h, wo.astype(dt))

        def one_group(tok, eidx, gw, n_tokens):
            buf, (src, w) = _dispatch_tokens(tok, eidx, gw, E_loc, C)
            out = ffn(buf.astype(dt))
            flat = (out * w[..., None].astype(dt)).reshape(E_loc * C, D)
            srcf = src.reshape(E_loc * C)
            return jnp.zeros((n_tokens, D), dt).at[jnp.maximum(srcf, 0)].add(
                jnp.where(srcf[:, None] >= 0, flat, 0.0)
            )

        nb = xl.shape[0]
        if group == "seq":
            y = jax.vmap(lambda t, e, g: one_group(t, e, g, S))(xl, local_idx, gate_w)
        else:
            y = one_group(
                xl.reshape(nb * S, D), local_idx.reshape(nb * S, K),
                gate_w.reshape(nb * S, K), nb * S,
            ).reshape(nb, S, D)
        # psum combine dtype: f32 is the conservative baseline; 'bf16' halves
        # the EP all-reduce payload (kimi hillclimb). (XLA:CPU only aborts on
        # bf16 *copy-combiner* all-reduces; this is an add-combiner.)
        if getattr(cfg, "moe_combine_dtype", "f32") == "bf16":
            return jax.lax.psum(y.astype(jnp.bfloat16), "model").astype(dt)
        return jax.lax.psum(y.astype(jnp.float32), "model").astype(dt)

    # Boundary tensors cross in f32: the cotangent of a replicated shard_map
    # input lowers to a copy-combiner all-reduce, which XLA:CPU's
    # AllReducePromotion pass aborts on for bf16 (f32 is untouched). On TPU
    # this costs nothing extra at entry (no collective on replicated-in).
    manual = {"model", *data_axes}
    wi_spec = PartitionSpec("model", data_axes if fsdp else None, None, None)
    wo_spec = PartitionSpec("model", None, data_axes if fsdp else None)
    return _shard_map(
        body,
        mesh=mesh,
        in_specs=(
            PartitionSpec(batch_spec, None, None),  # x: batch rows local
            PartitionSpec(None, None),  # router replicated
            wi_spec,  # wi: experts over TP (+ FSDP rows over data)
            wo_spec,
        ),
        out_specs=PartitionSpec(batch_spec, None, None),
        axis_names=manual,
    )(x.astype(jnp.float32), p["router"], p["wi"], p["wo"])


def moe_apply(p: dict, x: jax.Array, cfg, *, group: str = "seq") -> jax.Array:
    """Top-k routed MoE FFN (SwiGLU experts), sort-based dispatch.

    group='seq'   : dispatch independently per sequence (train/prefill) --
                    capacity is per (sequence, expert), so dispatch indices
                    stay batch-local and the batch sharding is preserved.
    group='batch' : dispatch across the whole [B*S] token set (decode, S=1).

    With a mesh installed and E divisible by TP, dispatch runs expert-
    parallel under shard_map (see _moe_expert_parallel); otherwise the
    pure-GSPMD single-device path below.
    """
    B, S, D = x.shape
    E, K = cfg.moe_experts, cfg.moe_topk
    dt = cfg.compute_dtype

    mesh, _ = mesh_info()
    if (mesh is not None and E % max(_axis_product(mesh, "model"), 1) == 0
            and _axis_product(mesh, "model") > 1
            and getattr(cfg, "layout", "tp") == "tp"
            and not os.environ.get("REPRO_DISABLE_EP")):
        return _moe_expert_parallel(p, x, cfg, group, mesh)

    logits = jnp.einsum("bsd,de->bse", x, p["router"].astype(jnp.float32))
    gates = jax.nn.softmax(logits, axis=-1)
    gate_w, expert_idx = jax.lax.top_k(gates, K)  # [B,S,K]
    gate_w = gate_w / jnp.maximum(gate_w.sum(-1, keepdims=True), 1e-9)

    def ffn(buf):  # buf: [..., E, C, D]
        h = jnp.einsum("...ecd,edgf->...ecgf", buf, p["wi"].astype(dt))
        h = jax.nn.silu(h[..., 0, :]) * h[..., 1, :]
        return jnp.einsum("...ecf,efd->...ecd", h, p["wo"].astype(dt))

    if group == "seq":
        C = moe_capacity(cfg, S)

        def per_row(tok, eidx, gw):
            buf, (src, w) = _dispatch_tokens(tok, eidx, gw, E, C)
            return buf, src, w

        buf, src, w = jax.vmap(per_row)(x, expert_idx, gate_w)  # [B,E,C,D],[B,E,C]
        buf = shard(buf, "batch", "act_expert", None, None)
        out_buf = ffn(buf.astype(dt))
        out_buf = shard(out_buf, "batch", "act_expert", None, None)

        def combine(tok_out, src_row, w_row):
            flat = (tok_out * w_row[..., None].astype(dt)).reshape(E * C, D)
            srcf = src_row.reshape(E * C)
            y = jnp.zeros((S, D), dt).at[jnp.maximum(srcf, 0)].add(
                jnp.where(srcf[:, None] >= 0, flat, 0.0)
            )
            return y

        y = jax.vmap(combine)(out_buf, src, w)
    else:
        tok = x.reshape(B * S, D)
        C = moe_capacity(cfg, B * S)
        buf, (src, w) = _dispatch_tokens(tok, expert_idx.reshape(B * S, K), gate_w.reshape(B * S, K), E, C)
        buf = shard(buf, "act_expert", None, None)
        out_buf = ffn(buf.astype(dt))
        flat = (out_buf * w[..., None].astype(dt)).reshape(E * C, D)
        srcf = src.reshape(E * C)
        y = jnp.zeros((B * S, D), dt).at[jnp.maximum(srcf, 0)].add(
            jnp.where(srcf[:, None] >= 0, flat, 0.0)
        ).reshape(B, S, D)
    return shard(y, "batch", None, None)


def aux_load_balance_loss(p: dict, x: jax.Array, cfg) -> jax.Array:
    """Switch-style load-balancing auxiliary loss (used by train_step for MoE)."""
    logits = jnp.einsum("bsd,de->bse", x, p["router"].astype(jnp.float32))
    gates = jax.nn.softmax(logits, axis=-1)
    _, idx = jax.lax.top_k(gates, cfg.moe_topk)
    E = cfg.moe_experts
    hits = jax.nn.one_hot(idx, E).sum(axis=(-3, -2))  # [B? ...] -> per expert counts
    frac_tokens = hits / jnp.maximum(hits.sum(-1, keepdims=True), 1.0)
    frac_probs = gates.mean(axis=-2)
    return E * jnp.mean(jnp.sum(frac_tokens * frac_probs, axis=-1))
