"""Device-resident online consolidation engine (paper §V + §VIII as one scan).

This is the array-native runtime half that ``binpack_jax`` lacks: the full
arrive -> score -> place-or-queue -> run -> complete -> drain loop of the
paper's operating model, expressed as fixed-shape array state stepped by a
``jax.lax.while_loop`` (one micro-event per iteration, early exit when the
trace completes) so an entire arrival trace -- including completions and the
criterion-1 queue draining of §V -- runs jitted on device. The pure-Python
``core.scheduler.OnlineScheduler`` is the numpy reference oracle this module
is parity-tested against (tests/test_engine.py).

State encoding (m servers, K run-slots per server, n arrivals, T grid types):

  counts    : f32[m, T]  -- resident type counts (drives the Fig-8 scorer)
  comp      : f32[m]     -- Eqn-2 competing bytes, maintained incrementally
  col0      : f32[m, T]  -- additive-model column sums counts @ D (Eqn 3)
  colog_*   : f32[m, T]  -- counts @ log(1 - d) under the keep/lost cache
                            outcome (ground-truth co-run slowdown sums)
  slot_type : i32[m, K]  -- grid type per run slot (-1 = free)
  slot_rem  : f32[m, K]  -- remaining bytes per slot
  slot_arr  : i32[m, K]  -- arrival index occupying the slot
  queued    : bool[n]    -- criterion-1 queue; order == arrival order, which
                            matches the oracle because workloads are enqueued
                            in arrival order and never re-queued (a mask is
                            therefore equivalent to a ring buffer here)

The incremental sums make every event O(T) per server instead of O(T^2):
placing/finishing a type-t workload on server s adds/subtracts one row of
D[s] (model) and of log(1-d_s) (ground truth) -- the engine never re-reduces
the full [m, T, T] tensors inside the scan.

Each scan step consumes exactly one micro-event, picked by `lax.switch`:

  DRAIN  -- after a completion (or when the cluster idles with a non-empty
            queue), score *all* queued candidates against all servers in one
            batched call to the scoring interface and place the first
            (lowest arrival index) feasible one; repeat until none fits.
            Correct single-placement-per-step semantics because adding a
            workload never makes another candidate feasible (both criteria
            are monotone in additions).
  FINISH -- advance time to the earliest completion, free its slot, then
            switch to DRAIN ("most probably upon completion of another
            workload", §V).
  ARRIVE -- advance time to the next arrival, run the Fig-8 greedy on it,
            queue it if no server passes both criteria.

Ground-truth rates (the oracle's ``simulate_corun``) are reproduced exactly
for grid-typed workloads: pairwise slowdown factors compose multiplicatively,
so with per-type counts c the log co-run slowdown of a type-t workload on
server s is

  log T_t / T_base,t = sum_u c_u * log(1 - d_s[u, t]) - log(1 - d_s[t, t])

with the keep/lost variant of ``d_s`` (and of the base throughput) selected
by the server's *physical* cache state (Eqn 2 vs llc_tolerance * CacheSize).
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Callable, NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..obs import metrics as obs_metrics
from ..obs import recorder as obs_recorder
from .binpack_jax import (
    PackedCluster,
    _choose_from_scores,
    argmin_with_margin,
    score_candidates_jnp,
    server_loads,
)
from .contention import pair_slowdown_matrices, type_tables
from .server import ServerSpec

QUEUED = -1  # placement sentinel, same as binpack_jax

#: scoring backend signature: (cluster, counts [m,T], wtypes [Q]) ->
#: (cache_after [Q, m], maxd_after [Q, m])
Scorer = Callable[[PackedCluster, jax.Array, jax.Array], tuple[jax.Array, jax.Array]]


@dataclasses.dataclass(frozen=True)
class PackedDynamics:
    """Per-type ground-truth rate tables (device-side ``simulate_corun``)."""

    solo: jax.Array  # f32[m, T] solo throughput (bytes/s)
    base_lost: jax.Array  # f32[m, T] throughput after losing the LLC
    log_keep: jax.Array  # f32[m, T, T] log(1 - d_keep[i, j])
    log_lost: jax.Array  # f32[m, T, T] log(1 - d_lost[i, j])
    comp_bytes: jax.Array  # f32[m, T] per-type competing bytes (Eqn 2 terms)
    tol_budget: jax.Array  # f32[m] llc_tolerance * CacheSize (physical TDP)

    @classmethod
    def build(cls, servers: Sequence[ServerSpec]) -> "PackedDynamics":
        tables, logs = {}, {}
        solo, lost, lkeep, llost, comp, tol = [], [], [], [], [], []
        for s in servers:
            # keyed by the frozen spec value (not name): identical specs share
            # one pass, same-name variants do not; the default grid also hits
            # contention.py's per-spec table cache
            if s not in tables:
                tables[s] = type_tables(s)
                logs[s] = pair_slowdown_matrices(s)
            tt, (d_keep, d_lost) = tables[s], logs[s]
            solo.append(tt["solo"])
            lost.append(tt["base_lost"])
            lkeep.append(np.log1p(-np.clip(d_keep, 0.0, 1.0 - 1e-9)))
            llost.append(np.log1p(-np.clip(d_lost, 0.0, 1.0 - 1e-9)))
            comp.append(tt["comp_bytes"])
            tol.append(s.llc_tolerance * s.llc_bytes)
        f32 = lambda x: jnp.asarray(np.stack(x), jnp.float32)
        return cls(f32(solo), f32(lost), f32(lkeep), f32(llost), f32(comp),
                   jnp.asarray(tol, jnp.float32))


jax.tree_util.register_pytree_node(
    PackedDynamics,
    lambda d: ((d.solo, d.base_lost, d.log_keep, d.log_lost, d.comp_bytes, d.tol_budget), None),
    lambda aux, ch: PackedDynamics(*ch),
)


class EngineState(NamedTuple):
    now: jax.Array  # f32 scalar simulation clock
    ai: jax.Array  # i32 next-arrival pointer
    counts: jax.Array  # f32[m, T]
    comp: jax.Array  # f32[m] competing bytes (Eqn 2 LHS), incremental
    col0: jax.Array  # f32[m, T] counts @ D, incremental
    colog_keep: jax.Array  # f32[m, T] counts @ log(1-d_keep), incremental
    colog_lost: jax.Array  # f32[m, T] counts @ log(1-d_lost), incremental
    slot_type: jax.Array  # i32[m, K]
    slot_rem: jax.Array  # f32[m, K]
    slot_arr: jax.Array  # i32[m, K]
    queued: jax.Array  # bool[n]
    was_queued: jax.Array  # bool[n] -- the §V queue *decision* per arrival
    placement: jax.Array  # i32[n] server index or QUEUED
    place_time: jax.Array  # f32[n]
    finish_time: jax.Array  # f32[n]
    makespan: jax.Array  # f32 scalar (time of latest completion)
    max_deg: jax.Array  # f32 scalar max *observed* (simulated) degradation
    draining: jax.Array  # bool -- queue re-check pending
    deadlock: jax.Array  # bool -- queued work that no empty server can take
    obs_co: jax.Array  # f32[n, T] time-integrated co-resident type counts
    obs_lost: jax.Array  # f32[n] time spent past the physical TDP
    obs_logr: jax.Array  # f32[n] time-integrated log instantaneous rate
    # in-carry metrics plane; None (an empty pytree) unless metrics=True, so
    # the uninstrumented program is byte-identical to the pre-metrics jaxpr
    metrics: "obs_metrics.MetricFrame | None" = None
    # decision flight recorder (same off-switch contract as metrics)
    rec: "obs_recorder.RecState | None" = None


class EngineTrace(NamedTuple):
    """Raw device-side result of :func:`run_trace` (arrival-sorted order)."""

    placement: jax.Array  # i32[n]
    was_queued: jax.Array  # bool[n]
    place_time: jax.Array  # f32[n]
    finish_time: jax.Array  # f32[n]
    makespan: jax.Array  # f32
    max_deg: jax.Array  # f32
    deadlock: jax.Array  # bool
    obs_co: jax.Array  # f32[n, T] (zeros unless telemetry=True)
    obs_lost: jax.Array  # f32[n] (zeros unless telemetry=True)
    obs_logr: jax.Array  # f32[n] (zeros unless telemetry=True)
    metrics: "obs_metrics.MetricFrame | None" = None  # None unless metrics=True
    rec: "obs_recorder.RecState | None" = None  # None unless record=True


def corun_rates(
    cluster: PackedCluster, dyn: PackedDynamics, counts: jax.Array, slot_type: jax.Array
) -> jax.Array:
    """Ground-truth bytes/s per run slot under the current co-run sets [m, K].

    Standalone (counts-based) form of the rate model the scan maintains
    incrementally; exported for tests and one-off evaluations.
    """
    overflow = (counts * dyn.comp_bytes).sum(-1) > dyn.tol_budget  # [m] physical TDP
    ck = jnp.einsum("mt,mtu->mu", counts, dyn.log_keep)
    cl = jnp.einsum("mt,mtu->mu", counts, dyn.log_lost)
    ldiag_keep = jnp.diagonal(dyn.log_keep, axis1=1, axis2=2)
    ldiag_lost = jnp.diagonal(dyn.log_lost, axis1=1, axis2=2)
    return _slot_rates(dyn, ldiag_keep, ldiag_lost, overflow, ck, cl, slot_type)


def _slot_rates(dyn, ldiag_keep, ldiag_lost, overflow, colog_keep, colog_lost, slot_type):
    """Per-slot rates from the maintained log-slowdown sums."""
    colog = jnp.where(overflow[:, None], colog_lost, colog_keep)  # [m, T]
    ldiag = jnp.where(overflow[:, None], ldiag_lost, ldiag_keep)  # [m, T]
    base = jnp.where(overflow[:, None], dyn.base_lost, dyn.solo)  # [m, T]
    t = jnp.clip(slot_type, 0)  # [m, K]
    logslow = jnp.take_along_axis(colog - ldiag, t, axis=1)
    return jnp.take_along_axis(base, t, axis=1) * jnp.exp(logslow)  # [m, K]


def _trace_segment(
    cluster: PackedCluster,
    dyn: PackedDynamics,
    arr_time: jax.Array,  # f32[n], non-decreasing over the first n_valid
    arr_type: jax.Array,  # i32[n] grid types
    arr_bytes: jax.Array,  # f32[n] data_total per arrival
    n_valid: jax.Array,  # i32 scalar: arrivals actually present (<= n)
    *,
    objective: str = "sum_avg",
    scorer: Scorer | None = None,
    n_steps: int | None = None,
    telemetry: bool = False,
    metrics: bool = False,
    record: bool = False,
    rec: "obs_recorder.RecState | None" = None,
    rec_ctx: "obs_recorder.RecCtx | None" = None,
    axis=None,
) -> EngineTrace:
    """Trace body of :func:`run_trace`, with a *traced* arrival count.

    ``n = arr_time.shape[0]`` stays the static capacity (slot counts, step
    budget, scatter sentinels), while ``n_valid`` bounds how many arrivals
    the event loop consumes. The device-resident closed loop
    (``core.closed_loop``) scans this body over segments whose real size
    varies per step inside one fixed-capacity compilation; padding rows past
    ``n_valid`` are never arrived, so their trace outputs keep the initial
    sentinels (placement QUEUED, finish inf) and ``n_valid = 0`` exits at
    iteration zero. Plain (un-jitted) so callers embed it in their own jit.

    With a sharded ``axis`` this is the *per-shard* body (the caller runs it
    under ``shard_map``): ``cluster``/``dyn`` carry the local server slice,
    arrival arrays and the queue replicate, placements are global server
    indices, and per-micro-event globals (earliest finish, any-active,
    argmin-with-margin winners) cross the mesh as scalar ``pmin``/``psum``
    pairs. ``axis=None`` (or a dense axis) leaves every code path byte-for-
    byte identical to the unthreaded engine.
    """
    n = int(arr_time.shape[0])
    m, K = cluster.m, n
    if n_steps is None:
        n_steps = 4 * n + 8
    sharded = axis is not None and axis.is_sharded
    if sharded:
        lo = axis.offset(m)  # this shard's first global server index
        m_g = m * axis.shards
    else:
        lo, m_g = 0, m
    if record:
        rec0 = rec if rec is not None else obs_recorder.init(2 * n)
        ctx = rec_ctx if rec_ctx is not None else obs_recorder.default_ctx(
            m, m_g)
    else:
        rec0 = None

    diag = jnp.diagonal(cluster.D, axis1=1, axis2=2)  # [m, T]
    comp_delta = cluster.rs[None, :] + cluster.resident * cluster.fs[None, :]  # [m, T]
    ldiag_keep = jnp.diagonal(dyn.log_keep, axis1=1, axis2=2)  # [m, T]
    ldiag_lost = jnp.diagonal(dyn.log_lost, axis1=1, axis2=2)  # [m, T]
    T = cluster.T
    # all per-server sum tables side by side: one dynamic slice + one matvec
    # refreshes every maintained sum of the touched server (see apply_delta)
    tables = jnp.concatenate(
        [cluster.D, dyn.log_keep, dyn.log_lost, comp_delta[:, :, None]], axis=2
    )  # [m, T, 3T + 1]

    st0 = EngineState(
        now=jnp.float32(0.0),
        ai=jnp.int32(0),
        counts=jnp.zeros((m, cluster.T), jnp.float32),
        comp=jnp.zeros((m,), jnp.float32),
        col0=jnp.zeros((m, cluster.T), jnp.float32),
        colog_keep=jnp.zeros((m, cluster.T), jnp.float32),
        colog_lost=jnp.zeros((m, cluster.T), jnp.float32),
        slot_type=jnp.full((m, K), -1, jnp.int32),
        slot_rem=jnp.zeros((m, K), jnp.float32),
        slot_arr=jnp.full((m, K), -1, jnp.int32),
        queued=jnp.zeros((n,), bool),
        was_queued=jnp.zeros((n,), bool),
        placement=jnp.full((n,), QUEUED, jnp.int32),
        place_time=jnp.full((n,), -1.0, jnp.float32),
        finish_time=jnp.full((n,), jnp.inf, jnp.float32),
        makespan=jnp.float32(0.0),
        max_deg=jnp.float32(0.0),
        draining=jnp.asarray(False),
        deadlock=jnp.asarray(False),
        obs_co=jnp.zeros((n, cluster.T), jnp.float32),
        obs_lost=jnp.zeros((n,), jnp.float32),
        obs_logr=jnp.zeros((n,), jnp.float32),
        metrics=obs_metrics.zeros(m) if metrics else None,
        rec=rec0,
    )

    def score_fast(st, wtypes):
        """Shared scoring contract from the maintained sums (no einsum)."""
        delta = comp_delta[:, wtypes]  # [m, Q]
        cache_after = (st.comp[:, None] + delta) / cluster.llc_budget[:, None]
        col_after = st.col0[:, None, :] + cluster.D[:, wtypes, :]  # [m, Q, T]
        d_pred = jnp.clip(col_after - diag[:, None, :], 0.0, 1.0)
        onehot = jax.nn.one_hot(wtypes, cluster.T, dtype=st.counts.dtype)  # [Q, T]
        present = (st.counts[:, None, :] + onehot[None, :, :]) > 0
        maxd_after = jnp.max(jnp.where(present, d_pred, -jnp.inf), axis=-1)
        return cache_after.T, maxd_after.T  # [Q, m] each

    def loads_now(st):
        """(cache [m], maxd [m]) of the current state from the maintained sums."""
        cache = st.comp / cluster.llc_budget
        d_pred = jnp.clip(st.col0 - diag, 0.0, 1.0)
        present = st.counts > 0
        maxd = jnp.max(jnp.where(present, d_pred, -jnp.inf), axis=1)
        maxd = jnp.where(jnp.any(present, axis=1), maxd, 0.0)
        return cache, maxd

    @jax.named_scope("obs.score")
    def greedy_pick(st, wtypes):
        """Scoring + Fig-8 argmin (Table II / Fig-8 objective) for a batch."""
        wtypes = jnp.atleast_1d(wtypes)
        if scorer is None:
            cache_a, maxd_a = score_fast(st, wtypes)
        else:
            cache_a, maxd_a = scorer(cluster, st.counts, wtypes)
        # the fleet-health mask makes evicted servers infeasible on every
        # scoring backend (scores are computed, feasibility is vetoed here)
        feasible = ((maxd_a < cluster.degradation_limit) & (cache_a <= 1.0)
                    & (cluster.active > 0.5)[None, :])
        if objective == "sum_avg":  # Table II: minimize the load *increase*
            cache_now, maxd_now = loads_now(st)
            if scorer is None:
                # the cache increase is known in closed form; using it directly
                # avoids the f32 cancellation of (cache_after - cache_now)
                dcache = (comp_delta[:, wtypes] / cluster.llc_budget[:, None]).T
            else:
                dcache = cache_a - cache_now[None, :]
            score = 0.5 * (dcache + (maxd_a - maxd_now[None, :]))
        else:  # literal Fig 8: minimize the post-allocation average
            score = 0.5 * (cache_a + maxd_a)
        score = jnp.where(feasible, score, jnp.inf)
        if sharded:
            # score-local-then-argmin-allreduce: only (score, index) scalars
            # cross the mesh; tie-breaking is the dense first-global-index
            best, ok = _choose_from_scores(axis, score, m)
            return best, ok, score
        best = argmin_with_margin(score)  # oracle tie-breaking (lowest index)
        ok = jnp.any(feasible, axis=1)
        return jnp.where(ok, best, QUEUED), ok, score

    def apply_delta(st, server, wtype, sign):
        """counts update + canonical refresh of the touched server's sums.

        The sums are recomputed *from the counts row* (one [T] @ [T, T]
        matvec per table, only for the modified server) rather than updated
        incrementally: identical servers with identical co-run multisets then
        hold bitwise-identical sums regardless of event history, so score
        ties break by server index exactly like the float64 oracle's strict-
        improvement loop, and nothing drifts over long traces. ``sign=0`` is
        a no-op refresh (used when a conditional placement did not happen).

        ``server`` is a *global* index; on a sharded axis the owning shard
        rebases it and every other shard's writes fall off the scatter edge.
        """
        if sharded:
            s_l = server - lo
            owned = (s_l >= 0) & (s_l < m)
            s_safe = jnp.clip(s_l, 0, m - 1)
            sdst = jnp.where(owned, s_l, m)  # off-shard write drops
            counts = st.counts.at[sdst, wtype].add(sign)
            sums = counts[s_safe] @ tables[s_safe]
            return st._replace(
                counts=counts,
                comp=st.comp.at[sdst].set(sums[3 * T]),
                col0=st.col0.at[sdst].set(sums[:T]),
                colog_keep=st.colog_keep.at[sdst].set(sums[T:2 * T]),
                colog_lost=st.colog_lost.at[sdst].set(sums[2 * T:3 * T]),
            )
        counts = st.counts.at[server, wtype].add(sign)
        sums = counts[server] @ tables[server]  # [3T + 1]
        return st._replace(
            counts=counts,
            comp=st.comp.at[server].set(sums[3 * T]),
            col0=st.col0.at[server].set(sums[:T]),
            colog_keep=st.colog_keep.at[server].set(sums[T:2 * T]),
            colog_lost=st.colog_lost.at[server].set(sums[2 * T:3 * T]),
        )

    def place_if(st, found, idx, server, wtype, nbytes, t, queue_on_fail,
                 score_row=None):
        """Commit arrival ``idx`` to ``server`` when ``found``, else queue it.

        Conditional writes are expressed as scatters whose index is pushed
        out of bounds (and therefore dropped) on the untaken side -- much
        cheaper inside the event loop than materializing and merging two
        full states.

        ``score_row`` (record=True only) is the committed candidate's
        feasibility-masked score over this shard's servers -- the recorder's
        provenance for *why* this server won.
        """
        if record:
            server_g = jnp.where(found, server, QUEUED)
            qdepth = jnp.sum(st.queued, dtype=jnp.int32)
        server = jnp.where(found, server, 0)
        st = apply_delta(st, server, wtype, jnp.where(found, 1.0, 0.0))
        if sharded:
            # slot bookkeeping is owner-local: the owning shard picks the
            # free slot of its local row, everyone else's writes drop; the
            # replicated [n] queue/placement arrays take the same global
            # values on every shard
            s_l = jnp.clip(server - lo, 0, m - 1)
            owned = found & (server >= lo) & (server < lo + m)
            free = st.slot_type[s_l] < 0  # [K]
            k = jnp.where(owned, jnp.argmax(free), K)
            srow = jnp.where(owned, s_l, m)
        else:
            free = st.slot_type[server] < 0  # [K]
            k = jnp.where(found, jnp.argmax(free), K)  # K == n: a free slot exists
            srow = server
        on_place = jnp.where(found, idx, n)  # n / K index -> scatter dropped
        on_fail = jnp.where(found, n, idx) if queue_on_fail else n
        st = st._replace(
            slot_type=st.slot_type.at[srow, k].set(wtype),
            slot_rem=st.slot_rem.at[srow, k].set(nbytes),
            slot_arr=st.slot_arr.at[srow, k].set(idx),
            queued=st.queued.at[on_place].set(False).at[on_fail].set(True),
            was_queued=st.was_queued.at[on_fail].set(True),
            placement=st.placement.at[on_place].set(server),
            place_time=st.place_time.at[on_place].set(t),
        )
        if metrics or record:
            # Eqn-4 headroom of the committed server, post-commit: how much
            # of the degradation budget this placement left on the table
            if sharded:
                s_l = jnp.clip(server - lo, 0, m - 1)
                owned = (server >= lo) & (server < lo + m)
                d_pred = jnp.clip(st.col0[s_l] - diag[s_l], 0.0, 1.0)
                present = st.counts[s_l] > 0
                maxd_s = jnp.max(jnp.where(present, d_pred, -jnp.inf))
                maxd_s = jnp.where(jnp.any(present), maxd_s, 0.0)
                # single-owner broadcast: the consumers replicate
                maxd_s = axis.pmin(jnp.where(owned, maxd_s, jnp.inf))
            else:
                d_pred = jnp.clip(st.col0[server] - diag[server], 0.0, 1.0)
                present = st.counts[server] > 0
                maxd_s = jnp.max(jnp.where(present, d_pred, -jnp.inf))
                maxd_s = jnp.where(jnp.any(present), maxd_s, 0.0)
            headroom = cluster.degradation_limit - maxd_s
        if metrics:
            placed = found.astype(jnp.int32)
            mf = obs_metrics.count(st.metrics, "placements", placed)
            if queue_on_fail:  # arrival-time commit: the §V queue decision
                mf = obs_metrics.count(mf, "queued", 1 - placed)
            else:  # drain-window commit
                mf = obs_metrics.count(mf, "drain_placements", placed)
            w = found.astype(jnp.float32)
            mf = obs_metrics.observe(
                mf, "waiting_time", t - arr_time[jnp.clip(idx, 0, n - 1)],
                weight=w)
            if sharded:
                col = jax.nn.one_hot(
                    jnp.where(found & owned, s_l, m), m, dtype=jnp.float32)
            else:
                col = jax.nn.one_hot(
                    jnp.where(found, server, m), m, dtype=jnp.float32)
            mf = obs_metrics.observe(mf, "headroom", headroom, weight=w)
            mf = obs_metrics.add_server(mf, "placements", col)
            st = st._replace(metrics=mf)
        if record:
            # provenance row: candidates from the committed pick's score row
            # (all_gather-ed so every shard records the identical global
            # top-K), estimator/detector context owner-sampled at the chosen
            # server and pmin-broadcast like the headroom above
            score_g = axis.all_gather(score_row) if sharded else score_row
            cand, csc = obs_recorder.top_candidates(score_g)
            margin = obs_recorder.tie_margin(csc)
            if ctx.n_pair is None:
                npmin = jnp.float32(-1.0)
            elif sharded:
                rowi = jnp.clip(ctx.row_of[s_l], 0, ctx.n_pair.shape[0] - 1)
                val = obs_recorder.pair_exposure_min(
                    ctx.n_pair[rowi], st.counts[s_l], wtype)
                npmin = axis.pmin(jnp.where(owned, val, jnp.inf))
            else:
                rowi = jnp.clip(ctx.row_of[server], 0,
                                ctx.n_pair.shape[0] - 1)
                npmin = obs_recorder.pair_exposure_min(
                    ctx.n_pair[rowi], st.counts[server], wtype)
            if sharded:
                cus = axis.pmin(jnp.where(owned, ctx.cusum[s_l], jnp.inf))
            else:
                cus = ctx.cusum[server]
            if queue_on_fail:  # arrival-time decision: always one row
                rec_on = jnp.asarray(True)
                kind = jnp.where(found, obs_recorder.KIND_ARRIVE,
                                 obs_recorder.KIND_QUEUED)
            else:  # drain commit: a row only when something placed
                rec_on = found
                kind = jnp.int32(obs_recorder.KIND_DRAIN)
            st = st._replace(rec=obs_recorder.record_row(
                st.rec, on=rec_on, arrival=idx, segment=ctx.segment,
                server=server_g, kind=kind, qdepth=qdepth,
                pool_row=jnp.where(found, ctx.pool_row[server], -1),
                cand=cand, scores=csc, t=t,
                headroom=jnp.where(found, headroom, 0.0), margin=margin,
                n_pair_min=jnp.where(found, npmin, jnp.float32(-1.0)),
                cusum=jnp.where(found, cus, 0.0)))
        return st

    def advance(st, rates, dt):
        active = st.slot_type >= 0
        rem = jnp.where(active, jnp.maximum(st.slot_rem - rates * dt, 0.0), st.slot_rem)
        st = st._replace(slot_rem=rem)
        if telemetry:
            # integrate each running workload's co-resident counts, TDP
            # exposure, and log instantaneous rate over [now, now + dt). The
            # log-rate integral is what a fleet gets from sampling its
            # throughput counters: time-averaging log(rate) keeps the
            # estimator's log-linear model exact across within-run
            # co-residency changes (a plain bytes/duration rate mixes regimes
            # arithmetically). Per arrival, not per slot: a running arrival
            # is placed (on this shard) and not finished, and gathering its
            # server's row costs O(n T) where scattering every slot's row
            # cost O(m K T) -- seconds per segment at 1,024 servers on a TPU.
            srv = st.placement - lo if sharded else st.placement
            run = ((st.placement >= 0) & jnp.isinf(st.finish_time)
                   & (srv >= 0) & (srv < m))  # [n]
            s = jnp.clip(srv, 0, m - 1)
            t = jnp.clip(arr_type, 0, T - 1)
            co = jnp.maximum(
                st.counts[s] - jax.nn.one_hot(t, T, dtype=st.counts.dtype), 0.0)
            over = (st.comp > dyn.tol_budget)[s]  # [n] physical TDP
            # the arrival's rate, exactly as _slot_rates computes its slot's
            colog = jnp.where(over, st.colog_lost[s, t], st.colog_keep[s, t])
            ldiag = jnp.where(over, ldiag_lost[s, t], ldiag_keep[s, t])
            base = jnp.where(over, dyn.base_lost[s, t], dyn.solo[s, t])
            logr = jnp.log(base * jnp.exp(colog - ldiag))
            st = st._replace(
                obs_co=jnp.where(run[:, None], st.obs_co + dt * co, st.obs_co),
                obs_lost=jnp.where(run, st.obs_lost + dt * over, st.obs_lost),
                obs_logr=jnp.where(run, st.obs_logr + dt * logr, st.obs_logr),
            )
        return st

    W = min(8, n)  # drain fast-path window (first W queued candidates)

    @jax.named_scope("obs.drain")
    def drain_branch(st, rates, tt):
        del rates, tt
        # Queue order == arrival order (workloads are never re-queued), so the
        # first feasible *queued arrival index* is the item the oracle places.
        pos = jnp.cumsum(st.queued.astype(jnp.int32))  # 1-based rank among queued
        qlen = pos[-1]
        # arrival indices of the first W queued items (n where fewer than W)
        slot_of = jnp.where(st.queued & (pos <= W), pos - 1, W)
        widx = jnp.full((W + 1,), n, jnp.int32).at[slot_of].min(
            jnp.arange(n, dtype=jnp.int32))[:W]
        in_window = widx < n
        servers_w, ok_w, sc_w = greedy_pick(st, arr_type[jnp.clip(widx, 0, n - 1)])
        ok_w &= in_window
        found_w = jnp.any(ok_w)
        w_first = jnp.argmax(ok_w)
        q_w, srv_w = widx[w_first], servers_w[w_first]

        # the recorder needs the committed candidate's score row as well;
        # keeping it out of the cond when record=False preserves the
        # uninstrumented program structure
        def full_scan(_):
            # every window candidate failed but more are queued: score them all
            servers, ok, sc = greedy_pick(st, arr_type)  # [n]
            cand = st.queued & ok
            q = jnp.argmax(cand)
            out = (q, servers[q], jnp.any(cand))
            return out + (sc[q],) if record else out

        def window_hit(_):
            out = (q_w, srv_w, found_w)
            return out + (sc_w[w_first],) if record else out

        picked = jax.lax.cond(
            ~found_w & (qlen > W), full_scan, window_hit, operand=None)
        q, server, found = picked[:3]
        score_row = picked[3] if record else None

        st = place_if(st, found, q, server, arr_type[q], arr_bytes[q], st.now,
                      queue_on_fail=False, score_row=score_row)
        act_any = jnp.any(st.slot_type >= 0)
        if sharded:
            act_any = axis.any(act_any)
        no_active = ~act_any
        dead = ~found & no_active & (st.ai >= n_valid) & jnp.any(st.queued)
        if metrics:
            mf = obs_metrics.count(st.metrics, "drain_steps", 1)
            mf = obs_metrics.count(
                mf, "drain_full_scans", (~found_w & (qlen > W)).astype(jnp.int32))
            mf = obs_metrics.count(
                mf, "deadlocks", (dead & ~st.deadlock).astype(jnp.int32))
            st = st._replace(metrics=mf)
        return st._replace(draining=found, deadlock=st.deadlock | dead)

    @jax.named_scope("obs.finish")
    def finish_branch(st, rates, tt):
        # margin argmin: exactly-simultaneous completions (identical workloads
        # on same-spec servers) must resolve lowest-server-first like the
        # oracle's event loop; f32 noise would otherwise order them arbitrarily
        flat = tt.reshape(-1)
        if sharded:
            # the same margin-argmin, distributed: global min time by pmin,
            # local first-hit globalized by the shard's flat offset (global
            # flat order is (server, slot), so lo*K preserves it), then the
            # owning shard broadcasts the chosen slot's dt/arrival/type via
            # single-owner pmin reductions
            t_min = axis.pmin(jnp.min(flat))
            hit = flat <= t_min * (1.0 + 1e-5)
            k_loc = jnp.argmax(hit)
            g_flat = jnp.where(jnp.any(hit), lo * K + k_loc, m_g * K)
            k_flat_g = axis.pmin(g_flat)
            s_fin = k_flat_g // K  # global server index
            k_fin = k_flat_g % K
            s_l = jnp.clip(s_fin - lo, 0, m - 1)
            owned = (s_fin >= lo) & (s_fin < lo + m)
            dt = axis.pmin(jnp.where(
                owned, flat[jnp.clip(k_flat_g - lo * K, 0, m * K - 1)],
                jnp.inf))
            t_fin = st.now + dt
            st = advance(st, rates, t_fin - st.now)
            idx = axis.pmin(jnp.where(owned, st.slot_arr[s_l, k_fin], n))
            wtype = axis.pmin(jnp.where(owned, st.slot_type[s_l, k_fin], T))
            srow = jnp.where(owned, s_l, m)  # local clear; others drop
        else:
            t_min = jnp.min(flat)
            k_flat = jnp.argmax(flat <= t_min * (1.0 + 1e-5))
            s_fin, k_fin = k_flat // K, k_flat % K
            t_fin = st.now + flat[k_flat]
            st = advance(st, rates, t_fin - st.now)
            idx = st.slot_arr[s_fin, k_fin]
            wtype = st.slot_type[s_fin, k_fin]
            srow = s_fin
        st = apply_delta(st, s_fin, wtype, -1.0)
        if metrics:
            # observed slowdown = actual duration / solo duration on the
            # server that ran it -- the serving-SLO quantity next to waiting
            if sharded:
                srate = axis.pmin(jnp.where(
                    owned, dyn.solo[s_l, jnp.clip(wtype, 0)], jnp.inf))
                fin_col = jax.nn.one_hot(srow, m, dtype=jnp.float32)
            else:
                srate = dyn.solo[s_fin, jnp.clip(wtype, 0)]
                fin_col = jax.nn.one_hot(s_fin, m, dtype=jnp.float32)
            solo_dur = arr_bytes[jnp.clip(idx, 0, n - 1)] / jnp.maximum(
                srate, jnp.float32(1e-30))
            actual = t_fin - st.place_time[idx]
            mf = obs_metrics.count(st.metrics, "finishes", 1)
            mf = obs_metrics.observe(
                mf, "slowdown", actual / jnp.maximum(solo_dur, jnp.float32(1e-30)))
            mf = obs_metrics.add_server(mf, "finishes", fin_col)
            st = st._replace(metrics=mf)
        return st._replace(
            now=t_fin,
            makespan=t_fin,
            slot_type=st.slot_type.at[srow, k_fin].set(-1),
            slot_arr=st.slot_arr.at[srow, k_fin].set(-1),
            finish_time=st.finish_time.at[idx].set(t_fin),
            draining=jnp.any(st.queued),  # §V: completion may unblock the queue
        )

    @jax.named_scope("obs.arrive")
    def arrive_branch(st, rates, tt):
        del tt
        t_arr = arr_time[st.ai]
        st = advance(st, rates, t_arr - st.now)._replace(now=t_arr)
        if metrics:
            st = st._replace(metrics=obs_metrics.count(st.metrics, "arrivals", 1))
        wtype, nbytes = arr_type[st.ai], arr_bytes[st.ai]
        servers, ok, sc = greedy_pick(st, wtype[None])
        st = place_if(st, ok[0], st.ai, servers[0], wtype, nbytes, t_arr,
                      queue_on_fail=True, score_row=sc[0] if record else None)
        return st._replace(ai=st.ai + 1)

    def is_done(st):
        return st.deadlock | (
            (st.ai >= n_valid) & ~jnp.any(st.slot_type >= 0) & ~jnp.any(st.queued))

    @jax.named_scope("obs.rates")
    def pick_event(st):
        """Slot rates, observed degradation and finish times of the running
        set, and which micro-event comes next (0 drain, 1 finish, 2 arrive)."""
        overflow = st.comp > dyn.tol_budget
        rates = _slot_rates(dyn, ldiag_keep, ldiag_lost, overflow,
                            st.colog_keep, st.colog_lost, st.slot_type)
        active = st.slot_type >= 0
        # observed (ground-truth) degradation of the running set, for Fig-5 audits
        solo = jnp.take_along_axis(dyn.solo, jnp.clip(st.slot_type, 0), axis=1)
        deg = jnp.where(active, 1.0 - rates / solo, -jnp.inf)
        # per-shard running max when sharded; globalized once after the loop
        st = st._replace(max_deg=jnp.maximum(st.max_deg, jnp.max(deg, initial=-jnp.inf)))
        if metrics:
            qdepth = jnp.sum(st.queued, dtype=jnp.float32)
            mf = obs_metrics.count(st.metrics, "events", 1)
            mf = obs_metrics.observe(mf, "queue_depth", qdepth)
            mf = obs_metrics.gauge_max(mf, "queue_peak", qdepth)
            # utilization-floor violations: events where a slot's *observed*
            # degradation exceeded the paper's limit, per server
            mf = obs_metrics.add_server(
                mf, "floor_violations",
                jnp.any(deg > cluster.degradation_limit, axis=1).astype(jnp.float32))
            mf = obs_metrics.add_server(
                mf, "busy_events", jnp.any(active, axis=1).astype(jnp.float32))
            st = st._replace(metrics=mf)

        tt = jnp.where(active, st.slot_rem / rates, jnp.inf)
        t_fin_local = st.now + jnp.min(tt)
        t_arr = jnp.where(st.ai < n_valid, arr_time[jnp.clip(st.ai, 0, n - 1)], jnp.inf)
        if sharded:
            # the event picker needs fleet-wide scalars: earliest completion
            # anywhere, any slot busy anywhere. One pmin + one psum per
            # micro-event; the branch index then replicates, so every shard
            # enters the same lax.switch arm and collectives stay aligned.
            t_fin = axis.pmin(t_fin_local)
            any_active = axis.any(jnp.any(active))
        else:
            t_fin = t_fin_local
            any_active = jnp.any(active)
        queue_any = jnp.any(st.queued)
        drain = st.draining | (queue_any & ~any_active & (st.ai >= n_valid))
        branch = jnp.where(drain, 0, jnp.where(any_active & (t_fin <= t_arr), 1, 2))
        return st, rates, tt, branch

    def event_step(st):
        st, rates, tt, branch = pick_event(st)
        return jax.lax.switch(
            branch, [drain_branch, finish_branch, arrive_branch], st, rates, tt)

    if sharded:
        # collectives may not run in a while_loop's cond; carry the (fully
        # replicated) done flag computed at the end of each body instead
        def body(carry):
            st, it, _ = carry
            st = event_step(st)
            act_any = axis.any(jnp.any(st.slot_type >= 0))
            done = st.deadlock | (
                (st.ai >= n_valid) & ~act_any & ~jnp.any(st.queued))
            return st, it + 1, done

        def cond(carry):
            st, it, done = carry
            return (it < n_steps) & ~done

        st, _, _ = jax.lax.while_loop(
            cond, body, (st0, jnp.int32(0), jnp.int32(0) >= n_valid))
        max_deg = axis.pmax(st.max_deg)
        if telemetry:
            # each arrival's observation integrals accumulated on the single
            # shard owning its server: the psum is a plain gather, bit-exact
            st = st._replace(obs_co=axis.psum(st.obs_co),
                             obs_lost=axis.psum(st.obs_lost),
                             obs_logr=axis.psum(st.obs_logr))
        st = st._replace(max_deg=max_deg)
    else:
        def body(carry):
            st, it = carry
            return event_step(st), it + 1

        def cond(carry):
            st, it = carry
            return (it < n_steps) & ~is_done(st)

        st, _ = jax.lax.while_loop(cond, body, (st0, jnp.int32(0)))
    return EngineTrace(st.placement, st.was_queued, st.place_time, st.finish_time,
                       st.makespan, st.max_deg, st.deadlock, st.obs_co, st.obs_lost,
                       st.obs_logr, st.metrics, st.rec)


@partial(jax.jit,
         static_argnames=("objective", "scorer", "n_steps", "telemetry",
                          "metrics", "record", "axis"))
def run_trace(
    cluster: PackedCluster,
    dyn: PackedDynamics,
    arr_time: jax.Array,  # f32[n], non-decreasing
    arr_type: jax.Array,  # i32[n] grid types
    arr_bytes: jax.Array,  # f32[n] data_total per arrival
    *,
    objective: str = "sum_avg",
    scorer: Scorer | None = None,
    n_steps: int | None = None,
    telemetry: bool = False,
    metrics: bool = False,
    record: bool = False,
    rec: "obs_recorder.RecState | None" = None,
    rec_ctx: "obs_recorder.RecCtx | None" = None,
    axis=None,
) -> EngineTrace:
    """Run one arrival trace to completion entirely on device.

    Every iteration is one micro-event; 4n + 8 steps are provably enough (n
    arrivals, <= n completions, <= n successful drain placements, and one
    failed drain check per completion), the loop exits early once all work
    has completed, and the whole loop jit-compiles once per (m, n) shape.

    Placements and queue decisions reproduce the float64 oracle: canonical
    per-server sum refreshes keep same-spec servers bitwise-tied, and
    ``argmin_with_margin`` resolves sub-margin score/finish-time ties to the
    lowest index exactly like the oracle's strict-improvement loops.

    ``scorer=None`` uses the engine's incremental evaluation of the shared
    scoring contract (O(Q m T) with no counts @ D re-reduction); passing an
    explicit backend (e.g. the Pallas kernel via ``engine.make_scorer``)
    routes every candidate batch through it instead.

    ``telemetry=True`` additionally emits the fixed-shape observation log the
    streaming D-estimator consumes (``repro.telemetry``): per arrival, the
    time-integrated co-resident type counts over its run (``obs_co`` [n, T],
    excluding the workload itself) and the time it spent while its server was
    past the physical TDP (``obs_lost`` [n]). Both integrate between
    micro-events, so partial co-residency overlaps are weighted exactly by
    their duration. Off by default: the accumulation adds an O(m K T) scatter
    per time-advancing event, and the static flag compiles it out entirely.

    ``metrics=True`` threads an ``obs.MetricFrame`` through the event loop
    (queue depth per event, waiting time / Eqn-4 headroom at commit, drain
    occupancy, observed slowdown at finish, per-server floor violations) and
    returns it on ``EngineTrace.metrics``. Purely additive to the carry:
    decisions are unchanged, and with the flag off the slot is ``None`` --
    an empty pytree -- so the compiled program is byte-identical.

    ``record=True`` threads the decision flight recorder (``obs.recorder``)
    through the loop: one packed provenance row per placement commit or
    queue-at-arrival decision, returned on ``EngineTrace.rec``. Same
    off-switch contract as ``metrics``; recording never feeds back into
    scoring, so recorded runs stay decision-identical. ``rec`` continues an
    existing ring (defaults to a fresh ring of capacity 2n) and ``rec_ctx``
    supplies the estimator/detector context to sample (defaults to the
    no-estimator context).

    ``axis`` (a :class:`~repro.distributed.server_axis.ServerAxis`) shards
    every ``[m, ...]`` input over its mesh and runs the event loop SPMD:
    each shard scores and books its own servers, and only the per-event
    scalars (winning score/index, earliest finish, any-active) cross the
    mesh. ``None``/dense lowers to the byte-identical single-device program.
    """
    if axis is None or not axis.is_sharded:
        return _trace_segment(
            cluster, dyn, arr_time, arr_type, arr_bytes,
            jnp.int32(arr_time.shape[0]), objective=objective, scorer=scorer,
            n_steps=n_steps, telemetry=telemetry, metrics=metrics,
            record=record, rec=rec, rec_ctx=rec_ctx)

    m_g = cluster.m
    axis.validate(m_g)

    if record:
        # resolve defaults *outside* the shard_map so rec/rec_ctx arrive as
        # operands with well-defined specs (ctx rows shard, the ring
        # replicates)
        n = int(arr_time.shape[0])
        rec = rec if rec is not None else obs_recorder.init(2 * n)
        rec_ctx = rec_ctx if rec_ctx is not None else \
            obs_recorder.default_ctx(m_g, m_g)

        def seg(cluster_l, dyn_l, a_time, a_type, a_bytes, n_valid,
                rec_l, ctx_l):
            return _trace_segment(
                cluster_l, dyn_l, a_time, a_type, a_bytes, n_valid,
                objective=objective, scorer=scorer, n_steps=n_steps,
                telemetry=telemetry, metrics=metrics, record=True,
                rec=rec_l, rec_ctx=ctx_l, axis=axis)

        extra_in = (obs_recorder.rec_specs(axis),
                    obs_recorder.ctx_specs(axis, rec_ctx))
        extra_args = (rec, rec_ctx)
    else:
        def seg(cluster_l, dyn_l, a_time, a_type, a_bytes, n_valid):
            return _trace_segment(
                cluster_l, dyn_l, a_time, a_type, a_bytes, n_valid,
                objective=objective, scorer=scorer, n_steps=n_steps,
                telemetry=telemetry, metrics=metrics, axis=axis)

        extra_in = ()
        extra_args = ()

    out_specs = EngineTrace(
        placement=axis.rep(), was_queued=axis.rep(), place_time=axis.rep(),
        finish_time=axis.rep(), makespan=axis.rep(), max_deg=axis.rep(),
        deadlock=axis.rep(), obs_co=axis.rep(), obs_lost=axis.rep(),
        obs_logr=axis.rep(),
        metrics=obs_metrics.frame_specs(axis) if metrics else None,
        rec=obs_recorder.rec_specs(axis) if record else None)
    mapped = axis.shard_map(
        seg,
        in_specs=(axis.shard_leading(cluster, m_g),
                  axis.shard_leading(dyn, m_g),
                  axis.rep(), axis.rep(), axis.rep(), axis.rep()) + extra_in,
        out_specs=out_specs)
    return mapped(cluster, dyn, arr_time, arr_type, arr_bytes,
                  jnp.int32(arr_time.shape[0]), *extra_args)


# --- array-native local search (core/refine.py's device backend) ----------------

@partial(jax.jit, static_argnames=("max_iters",))
def local_search_jax(
    cluster: PackedCluster, counts: jax.Array, max_iters: int = 100
) -> tuple[jax.Array, jax.Array]:
    """Best-improvement hill-climb over single-workload relocations.

    The array counterpart of ``refine.local_search``'s relocation moves: every
    (source server s, resident type t, target server u) move is scored in one
    vectorized evaluation through the same incremental load algebra as the
    shared scorer, and the steepest feasible descent step is applied until no
    move improves the paper's global objective (sum of per-server average
    loads). Returns (counts, n_moves).
    """
    m, T = counts.shape
    diag = jnp.diagonal(cluster.D, axis1=1, axis2=2)  # [m, T]

    def loads_after_removal(c):
        """avg_load [m, T] of each server after removing one of each type.

        (The *addition* side is exactly the shared scorer over all T types;
        only removal needs its own algebra.)
        """
        comp0 = c @ cluster.rs + (c * cluster.resident) @ cluster.fs  # [m]
        delta = cluster.rs[None, :] + cluster.resident * cluster.fs[None, :]  # [m, T]
        cache = (comp0[:, None] - delta) / cluster.llc_budget[:, None]
        col0 = jnp.einsum("mt,mtu->mu", c, cluster.D)  # [m, T]
        col = col0[:, None, :] - cluster.D  # [m, T(moved), T]
        d_pred = jnp.clip(col - diag[:, None, :], 0.0, 1.0)
        present = (c[:, None, :] - jnp.eye(T, dtype=c.dtype)[None, :, :]) > 0
        maxd = jnp.max(jnp.where(present, d_pred, -jnp.inf), axis=-1)
        maxd = jnp.where(jnp.any(present, axis=-1), maxd, 0.0)
        return cache, maxd

    def body(carry):
        c, moves, improved = carry
        cache_now, maxd_now = server_loads(cluster, c)
        avg0 = 0.5 * (cache_now + maxd_now)  # [m]
        cache_rm, maxd_rm = loads_after_removal(c)  # [m, T]
        cache_ad, maxd_ad = (  # shared scorer: every type on every server
            a.T for a in score_candidates_jnp(cluster, c, jnp.arange(T)))
        avg_rm = 0.5 * (cache_rm + maxd_rm)
        avg_ad = 0.5 * (cache_ad + maxd_ad)
        # relocation targets honour the fleet-health mask like every other
        # scoring consumer: no move may land work on an evicted server
        feas_ad = ((maxd_ad < cluster.degradation_limit) & (cache_ad <= 1.0)
                   & (cluster.active > 0.5)[:, None])

        # delta[s, t, u] = objective change of moving one type-t from s to u
        delta = (avg_rm - avg0[:, None])[:, :, None] + (avg_ad - avg0[:, None]).T[None, :, :]
        valid = (c[:, :, None] > 0) & feas_ad.T[None, :, :]
        valid &= ~jnp.eye(m, dtype=bool)[:, None, :]
        delta = jnp.where(valid, delta, jnp.inf)
        flat = jnp.argmin(delta.reshape(-1))
        best = delta.reshape(-1)[flat]
        s, t, u = flat // (T * m), (flat // m) % T, flat % m
        improve = best < -1e-9
        c = jnp.where(improve, c.at[s, t].add(-1.0).at[u, t].add(1.0), c)
        return c, moves + improve.astype(jnp.int32), improve

    def cond(carry):
        _, moves, improved = carry
        return improved & (moves < max_iters)

    c, moves, _ = jax.lax.while_loop(
        cond, body, (counts, jnp.int32(0), jnp.asarray(True)))
    return c, moves
