"""The fully device-resident closed loop: all segments in one program.

``AdaptiveEngine.run`` (PR 2-6) alternates device and host every segment:
run the jitted event loop, pull telemetry, update the estimator bank, step
the drift detector, let the fleet controller split/evict, rebuild the
cluster from the new D estimate, dispatch the next segment. Each iteration
costs a dozen jit dispatches, an ``int()`` fence, and an m x [T, T] host
pull for ``estimate_D`` -- fixed overhead that dwarfs the device work once
segments are small and fleets are large.

:func:`run_closed_loop` folds the whole cycle into a single compiled
program: one ``lax.scan`` over segments whose carry holds everything the
host used to shuttle --

  bank       the stacked :class:`DeviceEstimatorState` (all estimator rows)
  det        the drift detector's :class:`CusumState`
  row_map /  the pool's update and read routing (``PooledEstimatorBank``'s
  read_row   ``row_of`` / ``_read_row`` as device arrays)
  active     the placement-eligibility mask
  seen       the controller's burn-in clock
  req_*      the requeue buffer (work evicted servers had in flight,
             re-injected at the head of the next segment)
  ring       the telemetry ring's buffer/cursor (``ObservationRing``)

and each step runs the segment's event loop (:func:`~repro.core.engine_jax`
``_trace_segment`` with a *traced* arrival count), folds the resulting
:class:`RingBlock` through the fused estimator update and CUSUM detector,
applies the controller's split/evict policy as pure array ops
(:func:`~repro.fleet.controller.fleet_step`), and re-schedules evicted
work -- no host anywhere in the loop.

Shapes are bucketed so warm runs never retrace: segments pad to a
power-of-two ``S_cap`` (masked by ``seg_valid``), arrivals per segment pad
to ``n_seg`` chunk rows plus ``n_seg`` requeue slots, and per-segment drift
is an index into a pre-stacked :class:`PackedDynamics` bank. The cluster's
structural tables are compiled once -- only ``D`` (re-blended from the
carried bank state each step, exactly ``estimate_D``'s confidence fallback)
and ``active`` vary -- which is also why this path requires drift that
leaves ``llc_bytes``/``llc_tolerance`` alone; richer drift belongs on the
host-alternating reference path, which remains the semantic oracle (see
DESIGN.md section 13 for when to prefer it).
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from ..fleet.controller import fleet_step
from ..fleet.detect import CusumState, _cusum_update
from ..obs import metrics as obs_metrics
from ..obs import recorder as obs_recorder
from ..telemetry.estimator import (
    DeviceEstimatorState,
    _bank_core,
    _blend_prior_t,
    _localize_block,
    _remap_rows,
)
from ..telemetry.log import RingBlock, _ring_write_masked, _rows_from_trace
from .binpack_jax import PackedCluster
from .engine_jax import PackedDynamics, Scorer, _trace_segment


@dataclasses.dataclass(frozen=True)
class ClosedLoopConfig:
    """Static (hashable -> compile-keyed) configuration of the fused loop.

    Engine policy (``objective``/``scorer``), the fleet controller's knobs,
    and the estimator hyperparameters all live here so the scan body closes
    over plain Python values -- one compilation per distinct policy, reused
    across runs and fleets of the same shape. ``scorer`` must be identity
    -stable (``make_scorer`` is lru-cached) or None for the default jnp
    scorer; ``fleet=False`` runs estimation only (no detector, no actions),
    mirroring a fleetless streaming ``AdaptiveEngine``.
    """

    objective: str = "sum_avg"
    scorer: Scorer | None = None
    fleet: bool = False
    # controller knobs (FleetController fields)
    warmup_segments: int = 2
    cusum_k: float = 0.25
    cusum_h: float = 2.0
    level_decay: float = 0.9
    fail_floor: float = 0.5
    min_exposure: float = 4.0
    det_max_lost_frac: float = 0.5
    # estimator hyperparameters (StreamingEstimator._hypers + the read blend)
    confidence_floor: float = 2.0
    lr: float = 0.6
    decay: float = 1.0
    step_damp: float = 0.5
    solo_eps: float = 0.05
    est_max_lost_frac: float = 0.5
    use_pallas: bool = False
    # thread an obs.MetricFrame through the carry (engine event metrics +
    # per-segment split/evict/requeue/ring/D-refresh accounting); off keeps
    # LoopCarry.metrics = None and the compiled program byte-identical
    metrics: bool = False
    # thread the decision flight recorder (obs.recorder) through the carry:
    # one provenance row per placement commit, sampling the estimator's
    # pair exposure / the detector's CUSUM level *as the scheduler saw
    # them* at segment entry; requires LoopCarry.rec to hold a real
    # RecState, and the same off-switch contract as metrics applies
    record: bool = False
    # server-axis layout (distributed.server_axis.ServerAxis): None or a
    # dense axis compiles the byte-identical single-device program; a
    # sharded axis runs the whole scan under shard_map with every [m, ...]
    # carry field sharded by server row and the queue/ring replicated
    axis: "object | None" = None


class LoopCarry(NamedTuple):
    """Everything the host used to shuttle between segments, as one pytree."""

    bank: DeviceEstimatorState  # stacked estimator rows [m, ...]
    det: CusumState  # drift detector state
    row_map: jax.Array  # i32[m] pool update routing (-1 = dropped)
    read_row: jax.Array  # i32[m] pool read routing (survives drops)
    active: jax.Array  # bool[m] placement eligibility
    seen: jax.Array  # i32 controller burn-in clock (segments observed)
    req_type: jax.Array  # i32[R] requeued arrival types
    req_bytes: jax.Array  # f32[R] requeued arrival sizes
    req_n: jax.Array  # i32 live requeue count (<= R)
    ring: RingBlock  # telemetry ring buffer [capacity, ...]
    ring_ptr: jax.Array  # i32 ring write cursor
    ring_total: jax.Array  # i32 rows ever pushed
    metrics: "obs_metrics.MetricFrame | None" = None  # in-carry metrics plane
    rec: "obs_recorder.RecState | None" = None  # in-carry decision recorder


class SegmentIn(NamedTuple):
    """Per-segment scan inputs, stacked [S_cap, ...] and padded."""

    arr_time: jax.Array  # f32[S, n_seg] chunk-relative times (t - t0_k)
    arr_type: jax.Array  # i32[S, n_seg] grid types
    arr_bytes: jax.Array  # f32[S, n_seg] data_total per arrival
    dyn_idx: jax.Array  # i32[S] index into the stacked PackedDynamics bank
    seg_valid: jax.Array  # bool[S] False = padding segment (no-op)


class SegmentOut(NamedTuple):
    """Per-segment scan outputs, stacked [S_cap, ...] by ``lax.scan``."""

    placement: jax.Array  # i32[n_cap] (-1 = never placed / padding)
    was_queued: jax.Array  # bool[n_cap]
    place_time: jax.Array  # f32[n_cap] chunk-relative
    finish_time: jax.Array  # f32[n_cap] chunk-relative
    makespan: jax.Array  # f32 chunk-relative
    max_deg: jax.Array  # f32
    deadlock: jax.Array  # bool (masked False on padding segments)
    used: jax.Array  # i32 telemetry rows the estimator consumed
    n_valid: jax.Array  # i32 arrivals this segment (requeue + chunk)
    n_requeued: jax.Array  # i32 requeued arrivals at segment entry
    req_overflow: jax.Array  # bool requeue demand exceeded capacity R
    split_fired: jax.Array  # bool[m]
    split_stat: jax.Array  # f32[m]
    evict_fired: jax.Array  # bool[m]
    evict_stat: jax.Array  # f32[m]
    evict_route: jax.Array  # bool[m] True = level route
    active_after: jax.Array  # bool[m] mask after this segment's actions


def _require_ring(rec) -> None:
    """Host-side structure check at trace time: a fresh ring minted inside
    the scan body would change the carry's structure between iterations --
    the caller owns the ring."""
    if rec is None:
        raise ValueError("config.record=True requires carry.rec to hold a "
                         "RecState (see obs.recorder.init)")


@partial(jax.jit, static_argnames=("config",))
def run_closed_loop(
    cluster: PackedCluster,
    dyn_stack: PackedDynamics,  # stacked [U, m, ...] per-segment dynamics
    Lp_t: jax.Array,  # f32[m, T, T] target-major L priors per estimator row
    logb_priors: jax.Array,  # f32[m, T] nominal log base priors per row
    carry: LoopCarry,
    xs: SegmentIn,
    config: ClosedLoopConfig,
) -> tuple[LoopCarry, SegmentOut]:
    """Scan the observe -> estimate -> detect -> act cycle over all segments.

    ``cluster`` supplies the structural tables only -- its ``D``/``active``
    are replaced inside every step from the carried bank state and mask.
    Returns the final carry (adopted wholesale by the host mirror) and the
    stacked per-segment outputs.
    """
    if config.record:
        _require_ring(carry.rec)
    m = int(carry.row_map.shape[0])
    R = int(carry.req_type.shape[0])
    n_seg = int(xs.arr_time.shape[1])
    n_cap = R + n_seg
    cap = int(carry.ring.ints.shape[0])
    axis = config.axis
    sharded = axis is not None and axis.is_sharded

    def _scan(cluster, dyn_stack, Lp_t, logb_priors, carry, xs):
        # per-shard body when sharded (each shard owns Lp_t.shape[0] server
        # rows; queue, ring and every decision array stay replicated); the
        # dense call traces the byte-identical single-device program
        m_l = int(Lp_t.shape[0])
        lo = axis.offset(m_l) if sharded else 0
        # the no-drift common case gathers the single dynamics once, outside
        # the scan body, instead of a [m, T, T]-sized dynamic gather per step
        dyn_0 = (jax.tree_util.tree_map(lambda a: a[0], dyn_stack)
                 if int(dyn_stack.solo.shape[0]) == 1 else None)

        def local_rows(read_row):
            """This shard's slice of a global server->row map, rebased to
            local row indices (pool locality keeps every value in range)."""
            if sharded:
                return jnp.clip(
                    jax.lax.dynamic_slice_in_dim(read_row, lo, m_l) - lo,
                    0, m_l - 1)
            return jnp.clip(read_row, 0, m - 1)

        def full_D(bank: DeviceEstimatorState, read_row) -> jax.Array:
            """estimate_D's confidence blend for every server, from scratch:
            blend in row space (elementwise ops commute with the row gather
            bit-for-bit), then one gather + transpose to scheduler layout."""
            L_eff_t = _blend_prior_t(bank.L_t, bank.n_pair_t,
                                     Lp_t, config.confidence_floor)
            D_rows = jnp.clip(-jnp.expm1(L_eff_t), 0.0, 0.999999)
            return D_rows[local_rows(read_row)].swapaxes(1, 2)

        def refresh_D(D, bank, read_row, a_type, block):
            """Re-blend only what this segment's telemetry can have moved.

            Without forgetting (``decay >= 1``) an update touches the bank
            only at the (row, type-column) pairs the block names, so ``D``
            needs new values only in those columns -- conservatively
            recomputed for every server (an untouched entry recomputes to
            the identical value). With forgetting the whole confidence row
            moves each update and the blend recomputes in full.
            """
            if config.decay < 1.0:
                return full_D(bank, read_row)
            rr = local_rows(read_row)  # [m servers this shard]
            row = block.server  # remapped bank row per telemetry row [B]
            wt = a_type  # the types whose D columns can have moved [B]
            wtc = jnp.clip(wt, 0, cluster.T - 1)
            # blend just the touched columns, for every server: [m, B, T(u)]
            cols = _blend_prior_t(
                bank.L_t[rr[:, None], wtc[None, :]],
                bank.n_pair_t[rr[:, None], wtc[None, :]],
                Lp_t[rr[:, None], wtc[None, :]], config.confidence_floor)
            cols = jnp.clip(-jnp.expm1(cols), 0.0, 0.999999)
            # rows that updated nothing (dropped server / bad type) write OOB
            tt = jnp.where((wt >= 0) & (wt < cluster.T)
                           & (row >= 0) & (row < m), wt, cluster.T)
            return D.at[:, :, tt].set(cols.swapaxes(1, 2))

        def step(scarry, x):
            carry, D = scarry
            q = carry.req_n
            n_valid = jnp.where(x.seg_valid, q + n_seg, 0)

            # assemble the segment's arrivals: requeued work first (at the
            # chunk-relative origin, exactly where the host prepends it), then
            # the chunk rows; padding rows never arrive (time inf past n_valid)
            i = jnp.arange(n_cap, dtype=jnp.int32)
            is_req = i < q
            ci = jnp.clip(i - q, 0, n_seg - 1)
            ri = jnp.clip(i, 0, R - 1)
            a_time = jnp.where(is_req, 0.0,
                               jnp.where(i < q + n_seg, x.arr_time[ci], jnp.inf))
            a_type = jnp.where(is_req, carry.req_type[ri], x.arr_type[ci])
            a_bytes = jnp.where(is_req, carry.req_bytes[ri], x.arr_bytes[ci])

            # the scheduler's D for this segment rides the carry (maintained
            # incrementally by refresh_D; rebuilt by full_D on topology changes)
            act_k = (jax.lax.dynamic_slice_in_dim(carry.active, lo, m_l)
                     if sharded else carry.active)
            cluster_k = dataclasses.replace(
                cluster, D=D, active=act_k.astype(jnp.float32))
            dyn_k = (dyn_0 if dyn_0 is not None else
                     jax.tree_util.tree_map(lambda a: a[x.dyn_idx], dyn_stack))

            # the segment's event loop, telemetry on
            if config.record:
                # sample the estimator/detector state the scheduler consults
                # *this* segment -- before the post-segment update below
                rec_ctx = obs_recorder.RecCtx(
                    n_pair=carry.bank.n_pair_t,
                    row_of=local_rows(carry.read_row),
                    cusum=carry.det.stat.max(axis=1),
                    pool_row=carry.read_row,
                    segment=carry.seen)
            else:
                rec_ctx = None
            with jax.named_scope("obs.segment_event_loop"):
                trace = _trace_segment(
                    cluster_k, dyn_k, a_time, a_type, a_bytes, n_valid,
                    objective=config.objective, scorer=config.scorer,
                    telemetry=True, metrics=config.metrics,
                    record=config.record, rec=carry.rec, rec_ctx=rec_ctx,
                    axis=axis)

            # observe -> estimate: the same fused banked update the host path
            # dispatches (remap through the pool routing, fold the block);
            # sparse_tables keeps the in-scan cost at O(B T) per step
            with jax.named_scope("obs.estimate"):
                block = _rows_from_trace(trace, a_type)
                rblock = _remap_rows(block, carry.row_map)
                bank, used = _bank_core(
                    carry.bank,
                    _localize_block(rblock, lo) if sharded else rblock,
                    lr=config.lr, decay=config.decay, step_damp=config.step_damp,
                    solo_eps=config.solo_eps, max_lost_frac=config.est_max_lost_frac,
                    use_pallas=config.use_pallas,
                    # the Pallas scatter interprets off-TPU only: on the
                    # chip it always lowers through Mosaic
                    interpret=jax.default_backend() != "tpu",
                    sparse_tables=True)
                if sharded:
                    used = axis.psum(used)

            seen = carry.seen + x.seg_valid.astype(jnp.int32)
            if config.fleet:
                # detect against the *post-update* pooled model, on the
                # original (un-remapped) block -- FleetController.observe's
                # exact order; each shard folds its own servers' rows
                # (pool locality keeps row_map shard-local)
                with jax.named_scope("obs.detect"):
                    if sharded:
                        det_row_map = (jax.lax.dynamic_slice_in_dim(
                            carry.row_map, lo, m_l) - lo)
                        det_block = _localize_block(block, lo)
                    else:
                        det_row_map, det_block = carry.row_map, block
                    det, _ = _cusum_update(
                        carry.det, det_block, bank.log_b, bank.L_t, det_row_map,
                        k=config.cusum_k, level_decay=config.level_decay,
                        max_lost_frac=config.det_max_lost_frac)
                    # burn-in: discard detector evidence, withhold actions
                    in_warmup = seen <= config.warmup_segments
                    det = jax.tree_util.tree_map(
                        lambda a: jnp.where(in_warmup, jnp.zeros_like(a), a), det)
                    out = fleet_step(
                        bank, det, carry.row_map, carry.read_row, carry.active,
                        logb_priors, x.seg_valid & ~in_warmup,
                        h=config.cusum_h, level_decay=config.level_decay,
                        fail_floor=config.fail_floor,
                        min_exposure=config.min_exposure, axis=axis)
                    bank, det = out.bank, out.det
                row_map, read_row, active = out.row_map, out.read_row, out.active
                split_fired, split_stat = out.split_fired, out.split_stat
                evict_fired, evict_stat = out.evict_fired, out.evict_stat
                evict_route = out.evict_route
                # topology changes remap reads/copy rows: rebuild D outright;
                # otherwise refresh just this segment's touched columns
                with jax.named_scope("obs.d_refresh"):
                    D = jax.lax.cond(
                        jnp.any(split_fired) | jnp.any(evict_fired),
                        lambda d: full_D(bank, read_row),
                        lambda d: refresh_D(d, bank, read_row, a_type, rblock),
                        D)
            else:
                det = carry.det
                row_map, read_row, active = (
                    carry.row_map, carry.read_row, carry.active)
                split_fired = evict_fired = evict_route = jnp.zeros((m,), bool)
                split_stat = evict_stat = jnp.zeros((m,), jnp.float32)
                with jax.named_scope("obs.d_refresh"):
                    D = refresh_D(D, bank, read_row, a_type, rblock)

            # act -> re-schedule: work an evicted server held (or that never
            # placed) re-enters at the head of the next segment, in row order
            # -- the host's requeue comprehension as a cumsum scatter
            any_evict = jnp.any(evict_fired)
            pclip = jnp.clip(trace.placement, 0, m - 1)
            req_mask = ((i < n_valid) & any_evict
                        & (((trace.placement >= 0) & evict_fired[pclip])
                           | (trace.placement < 0)))
            pos = jnp.cumsum(req_mask.astype(jnp.int32)) - 1
            n_req = req_mask.sum()
            dst = jnp.where(req_mask & (pos < R), pos, R)
            req_type = jnp.zeros((R + 1,), jnp.int32).at[dst].set(a_type)[:R]
            req_bytes = jnp.ones((R + 1,), jnp.float32).at[dst].set(a_bytes)[:R]

            # mirror the host's per-segment ring push (the full block, valid
            # and invalid rows alike -- exactly n_valid rows land)
            ring = _ring_write_masked(carry.ring, block, carry.ring_ptr, n_valid)

            req_cnt = jnp.minimum(n_req, R)
            if config.metrics:
                # fold the segment's engine frame into the run frame, then add
                # the closed-loop-level accounting the host used to keep
                mf = obs_metrics.merge(carry.metrics, trace.metrics)
                mf = obs_metrics.count(mf, "segments", x.seg_valid.astype(jnp.int32))
                mf = obs_metrics.count(mf, "splits",
                                       jnp.sum(split_fired, dtype=jnp.int32))
                mf = obs_metrics.count(mf, "evictions",
                                       jnp.sum(evict_fired, dtype=jnp.int32))
                mf = obs_metrics.count(mf, "requeues", req_cnt)
                mf = obs_metrics.count(mf, "ring_rows", n_valid)
                # extent of the incremental D re-blend: block rows naming a
                # live (bank row, type) pair -- the columns refresh_D targets
                touched = jnp.sum((a_type >= 0) & (a_type < cluster.T)
                                  & (rblock.server >= 0) & (rblock.server < m),
                                  dtype=jnp.int32)
                mf = obs_metrics.count(mf, "d_cols_refreshed", touched)
                if config.fleet:
                    mf = obs_metrics.observe(
                        mf, "cusum_level", split_stat,
                        weight=(carry.active & x.seg_valid).astype(jnp.float32))
                mf = obs_metrics.gauge_max(
                    mf, "ring_occupancy_peak",
                    jnp.minimum(carry.ring_total + n_valid, cap).astype(jnp.float32))
                mf = obs_metrics.gauge_max(
                    mf, "evicted_peak", jnp.sum(~active, dtype=jnp.float32))
                mf = obs_metrics.gauge_max(
                    mf, "requeue_peak", req_cnt.astype(jnp.float32))
            else:
                mf = carry.metrics

            carry2 = LoopCarry(
                bank=bank, det=det, row_map=row_map, read_row=read_row,
                active=active, seen=seen,
                req_type=req_type, req_bytes=req_bytes,
                req_n=req_cnt,
                ring=ring, ring_ptr=(carry.ring_ptr + n_valid) % cap,
                ring_total=carry.ring_total + n_valid,
                metrics=mf,
                rec=trace.rec if config.record else carry.rec)
            out_k = SegmentOut(
                placement=trace.placement, was_queued=trace.was_queued,
                place_time=trace.place_time, finish_time=trace.finish_time,
                makespan=trace.makespan, max_deg=trace.max_deg,
                deadlock=trace.deadlock & x.seg_valid,
                used=used, n_valid=n_valid, n_requeued=q,
                req_overflow=(n_req > R) & x.seg_valid,
                split_fired=split_fired, split_stat=split_stat,
                evict_fired=evict_fired, evict_stat=evict_stat,
                evict_route=evict_route, active_after=active)
            return (carry2, D), out_k

        (carry, _), ys = jax.lax.scan(step, (carry, full_D(carry.bank,
                                                           carry.read_row)), xs)
        return carry, ys

    if not sharded:
        return _scan(cluster, dyn_stack, Lp_t, logb_priors, carry, xs)

    # one shard_map around the whole scan: [m, ...] state shards by server
    # row, the queue/ring/decision plane replicates, and the per-segment
    # collectives inside the engine / fleet_step keep every shard's
    # replicated copies bitwise aligned
    axis.validate(m)
    from jax.sharding import PartitionSpec

    carry_specs = LoopCarry(
        bank=axis.shard_leading(carry.bank, m),
        det=axis.shard_leading(carry.det, m),
        row_map=axis.rep(), read_row=axis.rep(), active=axis.rep(),
        seen=axis.rep(), req_type=axis.rep(), req_bytes=axis.rep(),
        req_n=axis.rep(), ring=axis.rep_tree(carry.ring),
        ring_ptr=axis.rep(), ring_total=axis.rep(),
        metrics=(obs_metrics.frame_specs(axis)
                 if carry.metrics is not None else None),
        rec=(obs_recorder.rec_specs(axis)
             if carry.rec is not None else None))
    dyn_specs = jax.tree_util.tree_map(
        lambda a: (PartitionSpec(None, axis.axis)
                   if a.ndim >= 2 and a.shape[1] == m else PartitionSpec()),
        dyn_stack)
    ys_specs = SegmentOut(*([axis.rep()] * len(SegmentOut._fields)))
    mapped = axis.shard_map(
        _scan,
        in_specs=(axis.shard_leading(cluster, m), dyn_specs, axis.spec(),
                  axis.rep(), carry_specs, axis.rep_tree(xs)),
        out_specs=(carry_specs, ys_specs))
    return mapped(cluster, dyn_stack, Lp_t, logb_priors, carry, xs)


# What the host reads of one run: every SegmentOut leaf but ``n_requeued``
# (no host code reads it), the ring cursor, and with a fleet controller
# the routing and mask its mirror adopts. The bank, detector, ring buffer,
# metrics frame and recorder stay on the device: the host adopts them as
# device arrays and never reads them.
_HOST_OUT = tuple(f for f in SegmentOut._fields if f != "n_requeued")
_HOST_CARRY = ("ring_ptr", "ring_total")
_HOST_FLEET = ("row_map", "read_row", "active")
_WORD_DTYPES = (np.dtype(np.int32), np.dtype(np.float32), np.dtype(np.bool_))


def _host_fields(final: LoopCarry, ys: SegmentOut, fleet: bool):
    """(name, array) of everything :func:`host_view` packs, in buffer order."""
    names = _HOST_CARRY + (_HOST_FLEET if fleet else ())
    return ([(f, getattr(ys, f)) for f in _HOST_OUT]
            + [(f, getattr(final, f)) for f in names])


def host_view_layout(final: LoopCarry, ys: SegmentOut, fleet: bool
                     ) -> tuple[tuple[str, int, tuple[int, ...], np.dtype], ...]:
    """Where :func:`host_view` puts each field: ``(name, offset, shape,
    dtype)``, offsets in 32-bit words. A function of the arrays' shapes and
    dtypes alone, so the host reads it without touching the device."""
    layout, off = [], 0
    for name, a in _host_fields(final, ys, fleet):
        dtype = np.dtype(a.dtype)
        if dtype not in _WORD_DTYPES:
            raise TypeError(f"host_view packs 32-bit words; {name} is {dtype}")
        layout.append((name, off, tuple(a.shape), dtype))
        off += math.prod(a.shape)
    return tuple(layout)


@partial(jax.jit, static_argnames=("fleet",))
def host_view(final: LoopCarry, ys: SegmentOut, fleet: bool) -> jax.Array:
    """Everything the host reads of one :func:`run_closed_loop` call as one
    flat ``int32`` buffer, so the run reaches the host in one
    device-to-host copy instead of one per array: each copy has a fixed
    cost that dwarfs the payload of a few KB.

    float32 fields travel as their bit patterns (``bitcast_convert_type``:
    NaN and inf exact), bools widened to 0/1. :func:`host_view_layout`
    gives each field's place; :func:`unpack_host_view` reads it back."""
    return jnp.concatenate([
        (jax.lax.bitcast_convert_type(a, jnp.int32) if a.dtype == jnp.float32
         else a.astype(jnp.int32)).reshape(-1)
        for _, a in _host_fields(final, ys, fleet)])


def unpack_host_view(words: np.ndarray, layout) -> dict[str, np.ndarray]:
    """The fields of a fetched :func:`host_view` buffer, by name: numpy
    views of ``words`` with each field's shape and dtype, bit for bit."""
    out = {}
    for name, off, shape, dtype in layout:
        w = words[off:off + math.prod(shape)]
        if dtype == np.float32:
            w = w.view(np.float32)
        elif dtype == np.bool_:
            w = w != 0
        out[name] = w.reshape(shape)
    return out
