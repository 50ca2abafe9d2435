"""ConsolidationEngine: one front-end over the consolidation runtime backends.

The repo used to have three disconnected consolidation paths:

  * ``core/binpack.py`` + ``core/scheduler.py`` -- pure-Python greedy and the
    event-driven ``OnlineScheduler`` (heapq + numpy);
  * ``core/binpack_jax.py`` -- the jitted greedy over arrival *sequences*,
    with no notion of time, completions, or queue draining;
  * ``kernels/consolidation.py`` -- the Pallas Q x m candidate scorer.

This module unifies them. ``ConsolidationEngine`` exposes the paper's full
online operating model (arrive -> score -> place-or-queue -> run -> complete
-> drain, §V/§VIII) behind one API with two runtime backends:

  backend='jax'    the device-resident ``engine_jax.run_trace`` scan;
  backend='numpy'  the demoted pure-Python ``OnlineScheduler``, kept as the
                   reference oracle the JAX engine is parity-tested against;
  backend='auto'   numpy below ``AUTO_JAX_THRESHOLD`` arrivals (jit overhead
                   dominates tiny traces), jax at scale.

Candidate scoring is a *separate* axis: all runtime backends consume the same
(counts, wtypes) -> (cache_after, maxd_after) scoring interface, provided by

  scorer='jnp'     ``binpack_jax.score_candidates_jnp`` (default, any device);
  scorer='pallas'  the Pallas kernel -- the fleet-scale Q x m path on TPU
                   (interpret mode elsewhere);
  scorer='numpy'   ``kernels.ref.consolidation_scores_ref`` -- host-side
                   float64 reference for contract tests (not jit-able).

``AdaptiveEngine`` closes the observe -> estimate -> schedule loop on top of
this: it feeds telemetry-enabled runs into streaming D-estimators
(``repro.telemetry``) and places each trace segment from the *estimated*
dynamics while the simulator stays ground truth (DESIGN.md §9).

See DESIGN.md §8 for the backend matrix and the architecture notes.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import TYPE_CHECKING, Callable, Literal, NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from .binpack import ClusterState, greedy_place
from .binpack_jax import PackedCluster, score_candidates_jnp
from .contention import profile_pairwise_fast, type_tables
from .engine_jax import QUEUED, PackedDynamics, Scorer, run_trace
from .scheduler import OnlineScheduler
from .server import ServerSpec
from .workload import FS_GRID, RS_GRID, Workload, type_index
from ..obs import metrics as obs_metrics
from ..obs import recorder as obs_recorder
from ..obs import trace as obs_trace
from ..obs.metrics import MetricFrame
from ..obs.recorder import DecisionRing
from ..telemetry.estimator import EstimatorBank, ScatterName, StreamingEstimator
from ..telemetry.log import (
    ObservationLog,
    ObservationRing,
    RingBlock,
    observations_from_trace,
    rows_from_trace,
)

if TYPE_CHECKING:
    from ..fleet.controller import FleetController, HealthEvent
    from .closed_loop import ClosedLoopConfig, LoopCarry, SegmentIn
    from ..telemetry.drift import DriftSchedule

Backend = Literal["auto", "jax", "numpy"]
ScorerName = Literal["jnp", "pallas", "numpy"]

#: below this many arrivals the oracle outruns a fresh jit compile
AUTO_JAX_THRESHOLD = 32


@functools.lru_cache(maxsize=None)
def make_scorer(backend: ScorerName = "jnp", interpret: bool | None = None) -> Scorer:
    """Resolve a scoring-backend name to the shared-interface callable.

    Cached so the returned closure is identity-stable -- ``run_trace`` treats
    the scorer as a static jit argument and would otherwise recompile per
    call.
    """
    if backend == "jnp":
        return score_candidates_jnp
    if backend == "pallas":
        from ..kernels.consolidation import consolidation_scores

        if interpret is None:
            interpret = jax.default_backend() != "tpu"

        def pallas_scorer(cluster, counts, wtypes):
            fs_res = cluster.resident * cluster.fs[None, :]
            return consolidation_scores(
                counts, cluster.D, cluster.rs, fs_res, cluster.llc_budget,
                jnp.atleast_1d(wtypes), interpret=interpret)

        return pallas_scorer
    if backend == "numpy":
        from ..kernels.ref import consolidation_scores_ref

        def numpy_scorer(cluster, counts, wtypes):
            return consolidation_scores_ref(
                counts, cluster.D, np.asarray(cluster.rs), np.asarray(cluster.fs),
                np.asarray(cluster.llc_budget), np.asarray(cluster.resident),
                jnp.atleast_1d(wtypes))

        return numpy_scorer
    raise ValueError(f"unknown scorer backend {backend!r}")


def score_candidates(
    cluster: PackedCluster, counts, wtypes, backend: ScorerName = "jnp"
) -> tuple[jax.Array, jax.Array]:
    """The shared scoring interface, dispatched by backend name."""
    return make_scorer(backend)(cluster, jnp.asarray(counts), jnp.asarray(wtypes))


@dataclasses.dataclass(frozen=True)
class EngineResult:
    """Backend-independent outcome of one arrival trace."""

    placements: tuple[int | None, ...]  # final server per arrival (None = never ran)
    was_queued: tuple[bool, ...]  # §V queue decision at arrival time
    place_times: tuple[float, ...]  # -1 where never placed
    finish_times: tuple[float, ...]  # +inf where never finished
    makespan: float
    max_observed_degradation: float
    backend: str
    observations: ObservationLog | None = None  # filled when run(telemetry=True)
    #: device-resident observation rows (run(telemetry='device')): the same
    #: records as ``observations`` but as a validity-masked RingBlock that
    #: never left the device -- what AdaptiveEngine's stream mode folds into
    #: its ObservationRing
    stream_block: RingBlock | None = None
    #: in-carry metrics plane (run(metrics=True)): queue depth, waiting time,
    #: Eqn-4 headroom, slowdown, per-server floor violations (repro.obs)
    metrics: MetricFrame | None = None
    #: decision flight recorder state (run(record=True)): one provenance row
    #: per placement commit / queue decision, in trace (arrival-sorted) order
    decisions: "obs_recorder.RecState | None" = None

    @property
    def queued_indices(self) -> tuple[int, ...]:
        return tuple(i for i, q in enumerate(self.was_queued) if q)


class ConsolidationEngine:
    """The unified online consolidation runtime (see module docstring)."""

    def __init__(
        self,
        servers: Sequence[ServerSpec],
        D: Sequence[np.ndarray] | np.ndarray | None = None,
        alpha: float | Sequence[float] = 1.3,
        objective: str = "sum_avg",
        backend: Backend = "auto",
        scorer: ScorerName = "jnp",
        active: Sequence[bool] | np.ndarray | None = None,
    ):
        if scorer == "numpy":
            # fail at construction, not at the trace length where 'auto'
            # happens to pick the jax runtime: the host-side float64 scorer
            # cannot run inside the jitted engine
            raise ValueError(
                "scorer='numpy' is the host-side float64 reference for "
                "score_candidates(); the engine runtimes take scorer "
                "'jnp' or 'pallas' (use backend='numpy' for the oracle)")
        self.servers = tuple(servers)
        if D is None:
            # keyed by the frozen spec value, not its name: same-name variant
            # specs (dataclasses.replace) must not share a profiling pass
            cache: dict[ServerSpec, np.ndarray] = {}
            for s in self.servers:  # identical specs share one profiling pass
                if s not in cache:
                    cache[s] = profile_pairwise_fast(s)
            D = [cache[s] for s in self.servers]
        elif isinstance(D, np.ndarray):
            D = [D] * len(self.servers)
        self.D = list(D)
        self.alpha = alpha
        self.objective = objective
        self.backend = backend
        self.scorer = scorer
        self._active: np.ndarray | None = (  # fleet-health placement mask
            None if active is None else np.asarray(active, bool))
        self.cluster = PackedCluster.build(
            list(self.servers), self.D, alpha, active=self._active)
        self._dyn: PackedDynamics | None = None

    @property
    def dyn(self) -> PackedDynamics:
        """Ground-truth rate tables, built on first device-backend use."""
        if self._dyn is None:
            self._dyn = PackedDynamics.build(self.servers)
        return self._dyn

    def set_D(
        self,
        D: Sequence[np.ndarray] | np.ndarray,
        active: Sequence[bool] | np.ndarray | None = None,
    ) -> None:
        """Swap the scoring D-matrices in place, rebuilding only what depends
        on them (the PackedCluster). The ground-truth ``PackedDynamics`` and
        the jitted trace programs key on server specs, not D, so a closed
        loop refreshing its estimate every segment pays for one [m, T, T]
        restack instead of a full engine rebuild. ``active`` optionally
        swaps the placement mask in the same build (the fleet loop updates
        both per segment; two separate calls would restack twice)."""
        if active is not None:
            self._active = self._check_mask(active)
        if isinstance(D, np.ndarray):
            D = [D] * len(self.servers)
        self.D = list(D)
        self.cluster = PackedCluster.build(
            list(self.servers), self.D, self.alpha, active=self._active)

    def _check_mask(self, active) -> np.ndarray:
        mask = np.asarray(active, bool)
        if mask.shape != (len(self.servers),):
            raise ValueError(
                f"active mask shape {mask.shape} != ({len(self.servers)},)")
        return mask

    def set_active(self, active: Sequence[bool] | np.ndarray) -> None:
        """Swap the fleet-health placement mask (True = eligible).

        Masked servers stay in every table -- shapes are unchanged, so the
        jitted trace programs are not re-traced -- but candidate scoring
        treats them as infeasible (``binpack_jax.greedy_choice`` and the
        engine's internal pick both veto them), so they receive no further
        placements. Masking lives in the device scoring path only: the numpy
        reference oracle does not consume it (``run`` refuses the
        combination).
        """
        mask = self._check_mask(active)
        if self._active is not None and np.array_equal(mask, self._active):
            return
        if self._active is None and mask.all():
            self._active = mask  # cluster is already all-active
            return
        self._active = mask
        self.cluster = PackedCluster.build(
            list(self.servers), self.D, self.alpha, active=mask)

    # -- public API -------------------------------------------------------
    def run(
        self,
        arrivals: Sequence[tuple[float, Workload]],
        backend: Backend | None = None,
        *,
        telemetry: bool | Literal["host", "device"] = False,
        metrics: bool = False,
        record: bool = False,
        rec: "obs_recorder.RecState | None" = None,
        rec_ctx: "obs_recorder.RecCtx | None" = None,
    ) -> EngineResult:
        """Simulate arrivals [(time, workload)] to completion of all work.

        Workloads are snapped to the profiling grid (as the paper's scheduler
        snaps every candidate for its D-matrix lookup); ``data_total`` is
        honoured per arrival. Raises ``RuntimeError`` on deadlock (a queued
        workload no *empty* server can take), like the oracle.

        ``telemetry=True`` (or ``'host'``) attaches the completion-observation
        log (``repro.telemetry.ObservationLog``) to the result -- the input
        of the streaming D-estimator's host path. ``'device'`` attaches the
        same records as a device-resident validity-masked ``stream_block``
        instead, never materializing a host log (the fleet-scale path:
        ``ObservationRing`` / ``StreamingEstimator.update_device``).
        Telemetry is emitted by the device engine's event loop, so it
        requires (and, under 'auto', selects) the jax backend.

        ``metrics=True`` threads the ``repro.obs`` MetricFrame through the
        event loop and attaches it as ``result.metrics`` (waiting-time /
        headroom / slowdown histograms, queue depth, per-server floor
        violations). Like telemetry, a device-engine feature: 'auto' selects
        jax for it.

        ``record=True`` threads the decision flight recorder through the
        event loop and attaches the resulting ring state as
        ``result.decisions`` (``obs.recorder``): one provenance row per
        placement commit or queue decision, decision-identical to an
        unrecorded run. ``rec`` continues an existing ring across calls and
        ``rec_ctx`` supplies estimator/detector context to sample; both
        default per run. A device-engine feature like the others.
        """
        if telemetry not in (False, True, "host", "device"):
            raise ValueError(f"unknown telemetry mode {telemetry!r}")
        backend = backend or self.backend
        masked = self._active is not None and not self._active.all()
        if backend == "auto":
            # telemetry, metrics, recording, and the fleet-health mask are
            # device-engine features: 'auto' selects jax for them regardless
            # of trace length
            backend = ("jax" if telemetry or masked or metrics or record
                       or len(arrivals) >= AUTO_JAX_THRESHOLD else "numpy")
        if backend not in ("jax", "numpy"):
            raise ValueError(f"unknown engine backend {backend!r}")
        if telemetry and backend != "jax":
            raise ValueError("telemetry requires the jax engine backend")
        if metrics and backend != "jax":
            raise ValueError("metrics requires the jax engine backend")
        if record and backend != "jax":
            raise ValueError("record requires the jax engine backend")
        if backend == "numpy" and masked:
            raise ValueError("server masking (set_active) requires the jax "
                             "engine backend; the numpy oracle has no mask")
        if not arrivals:
            obs = (ObservationLog.empty(self.cluster.T)
                   if telemetry in (True, "host") else None)
            frame = obs_metrics.zeros(len(self.servers)) if metrics else None
            return EngineResult((), (), (), (), 0.0, 0.0, backend, obs,
                                metrics=frame, decisions=rec if record else None)
        if backend == "jax":
            return self._run_jax(arrivals, telemetry=telemetry,
                                 metrics=metrics, record=record, rec=rec,
                                 rec_ctx=rec_ctx)
        return self._run_oracle(arrivals)

    # -- device backend ---------------------------------------------------
    def _run_jax(
        self,
        arrivals: Sequence[tuple[float, Workload]],
        telemetry: bool | Literal["host", "device"] = False,
        metrics: bool = False,
        record: bool = False,
        rec: "obs_recorder.RecState | None" = None,
        rec_ctx: "obs_recorder.RecCtx | None" = None,
    ) -> EngineResult:
        n = len(arrivals)
        times = np.asarray([t for t, _ in arrivals], np.float64)
        order = np.argsort(times, kind="stable")
        # normalize to the first arrival before the f32 cast: absolute
        # epoch-scale timestamps would otherwise collapse below f32 resolution
        t0 = float(times.min()) if n else 0.0
        arr_time = jnp.asarray(times[order] - t0, jnp.float32)
        arr_type = jnp.asarray([type_index(arrivals[i][1]) for i in order], jnp.int32)
        arr_bytes = jnp.asarray([arrivals[i][1].data_total for i in order], jnp.float32)

        # scorer='jnp' -> None: run_trace's incremental evaluation of the same
        # contract (no per-step counts @ D re-reduction); other backends are
        # routed through the generic interface.
        scorer = None if self.scorer == "jnp" else make_scorer(self.scorer)
        trace = run_trace(
            self.cluster, self.dyn, arr_time, arr_type, arr_bytes,
            objective=self.objective, scorer=scorer, telemetry=bool(telemetry),
            metrics=metrics, record=record, rec=rec, rec_ctx=rec_ctx)
        if bool(trace.deadlock):
            raise RuntimeError("deadlock: queued workloads fit no empty server")
        # observation records are per-run; the trace's arrival-sorted order is
        # as good as submission order, so no inverse permutation is needed
        obs = block = None
        if telemetry == "device":
            block = rows_from_trace(trace, arr_type)
        elif telemetry:
            obs = observations_from_trace(trace, arr_type, arr_bytes)

        inv = np.empty(n, np.int64)
        inv[order] = np.arange(n)
        placement = np.asarray(trace.placement)[inv]
        was_queued = np.asarray(trace.was_queued)[inv]
        place_time = np.asarray(trace.place_time, np.float64)[inv]
        finish_time = np.asarray(trace.finish_time, np.float64)[inv]
        place_time = np.where(place_time >= 0.0, place_time + t0, place_time)
        finish_time = np.where(np.isfinite(finish_time), finish_time + t0, finish_time)
        return EngineResult(
            placements=tuple(int(p) if p != QUEUED else None for p in placement),
            was_queued=tuple(bool(q) for q in was_queued),
            place_times=tuple(float(t) for t in place_time),
            finish_times=tuple(float(t) for t in finish_time),
            makespan=float(trace.makespan) + t0,
            max_observed_degradation=float(trace.max_deg),
            backend="jax",
            observations=obs,
            stream_block=block,
            metrics=trace.metrics,
            decisions=trace.rec,
        )

    # -- reference oracle -------------------------------------------------
    def _run_oracle(self, arrivals: Sequence[tuple[float, Workload]]) -> EngineResult:
        from .workload import snap_to_grid

        state = ClusterState.empty(list(self.servers), self.D, self.alpha)
        place = functools.partial(greedy_place, objective=self.objective)
        sched = OnlineScheduler(state, place=place)
        # distinct object identities per arrival so events map back uniquely
        # (callers may legitimately pass the same Workload object many times)
        copies = [(t, dataclasses.replace(snap_to_grid(w))) for t, w in arrivals]
        result = sched.run(copies)

        idx_of = {id(w): i for i, (_, w) in enumerate(copies)}
        n = len(copies)
        was_queued = [False] * n
        place_time = [-1.0] * n
        finish_time = [float("inf")] * n
        for e in result.events:
            i = idx_of.get(id(e.workload))
            if i is None:
                continue
            if e.kind == "queue":
                was_queued[i] = True
            elif e.kind == "place":
                place_time[i] = e.time
            elif e.kind == "finish":
                finish_time[i] = e.time
        return EngineResult(
            placements=tuple(result.placements[i] for i in range(n)),
            was_queued=tuple(was_queued),
            place_times=tuple(place_time),
            finish_times=tuple(finish_time),
            makespan=float(result.makespan),
            max_observed_degradation=float(result.max_observed_degradation),
            backend="numpy",
        )


# --- the closed observe -> estimate -> schedule loop ----------------------------

#: the paper's profiling grid size (10 RS x 23 FS)
GRID_T = len(RS_GRID) * len(FS_GRID)


@dataclasses.dataclass(frozen=True)
class AdaptiveResult:
    """Outcome of one :meth:`AdaptiveEngine.run`: per-segment engine results."""

    segments: tuple[EngineResult, ...]
    n_obs: tuple[int, ...]  # observations consumed by the estimators per segment
    t_starts: tuple[float, ...]  # first arrival time per segment
    #: fleet-health events fired after each segment (empty without a fleet
    #: controller): splits and evictions, in the order they were taken
    health: "tuple[tuple[HealthEvent, ...], ...]" = ()
    #: merged run-level MetricFrame (run(metrics=True)): the per-segment
    #: engine frames folded together plus the closed-loop accounting
    #: (segments/splits/evictions/requeues/ring occupancy). The counters
    #: shared with ``health`` match it exactly; the cusum_level histogram
    #: and d_cols_refreshed counter are device-loop-only (the host path
    #: rebuilds D wholesale and keeps detector stats in host objects)
    metrics: MetricFrame | None = None
    #: the engine's decision flight recorder after the run (run(record=True)):
    #: the host mirror whose ring holds every recorded placement decision,
    #: oldest overwritten first once capacity wraps (``obs.recorder``)
    decisions: "DecisionRing | None" = None

    @property
    def makespans(self) -> tuple[float, ...]:
        """Absolute completion time per segment (the engine's makespan)."""
        return tuple(r.makespan for r in self.segments)

    @property
    def durations(self) -> tuple[float, ...]:
        """First-arrival -> last-completion span per segment: the quantity
        comparable across segments (and against an oracle run of the same
        chunk), independent of where the chunk sits on the trace clock."""
        return tuple(r.makespan - t0 for r, t0 in zip(self.segments, self.t_starts))

    @property
    def total_obs(self) -> int:
        return int(sum(self.n_obs))


class DeviceLoopInputs(NamedTuple):
    """One device-loop run, packed: ``run_closed_loop(*inputs[:7])``."""

    cluster: PackedCluster
    dyn_stack: PackedDynamics  # stacked [U, m, ...] per-segment worlds
    Lp_t: jax.Array  # f32[m, T, T] target-major L priors
    logb_priors: jax.Array  # f32[m, T]
    carry: "LoopCarry"
    xs: "SegmentIn"
    config: "ClosedLoopConfig"
    t0s: tuple[float, ...]  # first arrival time per real segment


class AdaptiveEngine:
    """The closed-loop front-end: place from *estimated* dynamics, observe the
    (simulated) world, refresh the estimate, repeat.

    This is the first subsystem where the scheduler's model and the world can
    disagree. A :class:`ConsolidationEngine` consumes its D-matrix as frozen
    ground truth; here the D each placement consults comes from a per-server
    :class:`~repro.telemetry.StreamingEstimator` fed purely by completion
    observations, while the device engine's ``PackedDynamics`` (built from
    the *true* server specs, which a :class:`~repro.telemetry.DriftSchedule`
    may change under the scheduler) remains the ground truth that generates
    those observations.

    ``run`` splits the arrival trace into contiguous segments and alternates:
    run one segment to completion with the current estimate -> fold its
    observation log into the estimators -> rebuild D for the next segment.
    Each segment starts from an empty cluster, so segment makespans are
    directly comparable against a true-D oracle run under the same protocol
    (``benchmarks/adaptive_regret.py`` measures exactly that regret).

    ``stream=True`` is the fleet-scale variant of the same loop: each
    segment runs with ``telemetry='device'``, its observation rows fold into
    a shared device-resident :class:`~repro.telemetry.ObservationRing`, and
    every estimator refresh is one fused ``update_device`` call -- no host
    ``ObservationLog`` is ever materialized (DESIGN.md §10).

    ``fleet=FleetController(...)`` puts the fleet-health control plane
    (``repro.fleet``, DESIGN.md §11) in the loop, implying ``stream=True``:
    the controller binds to this engine's servers and estimators, same-spec
    servers pool onto shared estimator rows (warming up ~m x faster), each
    segment's telemetry block feeds the controller's CUSUM drift detector,
    and its decisions act on the very next segment -- split servers get
    their own seeded estimator, evicted servers are masked out of candidate
    scoring (``set_active``) and their in-flight workloads (placed on the
    evicted server in the detection segment, or never placed) are requeued
    into the following segment. Without a controller, estimators stay
    strictly per-server as before.
    """

    def __init__(
        self,
        servers: Sequence[ServerSpec],
        prior: float | str | np.ndarray | Sequence[np.ndarray] = 0.0,
        alpha: float | Sequence[float] = 1.3,
        objective: str = "sum_avg",
        scorer: ScorerName = "jnp",
        drift: "DriftSchedule | None" = None,
        lr: float = 0.6,
        decay: float = 1.0,
        confidence_floor: float = 2.0,
        max_lost_frac: float = 0.5,
        scatter: ScatterName = "auto",
        stream: bool = False,
        ring_capacity: int = 4096,
        fleet: "FleetController | None" = None,
        decision_capacity: int = 1024,
    ):
        """``prior`` selects what the scheduler believes before any telemetry:
        a scalar is a uniform D prior (0.0 = optimistic "no interference" --
        the fleet consolidates aggressively and learns the cost), 'profiled'
        seeds each estimator with the offline pairwise pass on the *initial*
        spec (stale once drift hits), and an array (or one per server) is an
        explicit prior. Solo base rates always start from the cheap per-type
        solo profile of the initial spec -- it is the 52 900-pair matrix, not
        the 230-run solo pass, that telemetry amortizes away."""
        self.servers = tuple(servers)
        self.alpha = alpha
        self.objective = objective
        self.scorer = scorer
        self.drift = drift
        self.fleet = fleet
        stream = stream or fleet is not None  # the control plane is stream-fed
        self.stream = stream
        self.ring = ObservationRing(ring_capacity, GRID_T) if stream else None
        # the decision flight recorder's host mirror, minted on the first
        # run(record=True) (capacity is spent in decisions, not segments)
        self.decision_capacity = int(decision_capacity)
        self.decisions: DecisionRing | None = None
        # segment-engine cache: under an unchanged world (drift is None, or a
        # schedule window with no event) only the D-matrices move between
        # segments, so the engine -- and with it the PackedDynamics tables and
        # the jitted trace programs keyed on them -- is reused via set_D.
        # Keyed by (specs, active-mask): drift schedules revisit worlds and
        # evictions change the mask between visits, and either a single-slot
        # cache or a specs-only key would rebuild (or cross-wire) engines on
        # the revisit. PackedDynamics is mask-independent, so it caches on
        # specs alone and is shared by all mask variants of a world.
        self._engine_cache: dict[tuple, ConsolidationEngine] = {}
        self._dyn_cache: dict[tuple[ServerSpec, ...], PackedDynamics] = {}
        # the device loop's loop-invariant inputs (_pack_device_loop): the
        # cluster and stacked dynamics keyed by (per-segment worlds, alpha),
        # the estimator priors in one slot built on first use
        self._loop_tables: dict[tuple, tuple[PackedCluster, PackedDynamics]] = {}
        self._loop_priors: "tuple[jax.Array, jax.Array] | None" = None
        #: device-loop runs that found their tables built (hits) or built
        #: them (misses): one miss per distinct (worlds, alpha)
        self.loop_tables_stats = {"hits": 0, "misses": 0}

        priors: list[np.ndarray | float]
        if isinstance(prior, str):
            if prior != "profiled":
                raise ValueError(f"unknown prior {prior!r}")
            cache: dict[ServerSpec, np.ndarray] = {}
            for s in self.servers:
                if s not in cache:
                    cache[s] = profile_pairwise_fast(s)
            priors = [cache[s] for s in self.servers]
        elif isinstance(prior, (int, float)):
            priors = [float(prior)] * len(self.servers)
        elif isinstance(prior, np.ndarray):
            priors = [prior] * len(self.servers)
        else:
            priors = list(prior)

        self.estimators = [
            StreamingEstimator(
                T=GRID_T,
                prior_D=priors[i],
                prior_solo=type_tables(s)["solo"],
                lr=lr,
                decay=decay,
                confidence_floor=confidence_floor,
                max_lost_frac=max_lost_frac,
                scatter=scatter,
            )
            for i, s in enumerate(self.servers)
        ]
        #: stream mode refreshes every server's estimator in one fused call;
        #: with a fleet controller the controller's pooled bank is that call
        #: (two banks over the same estimators would fight for their state)
        if fleet is not None:
            fleet.bind(self.servers, self.estimators)
            self.bank = None
        else:
            self.bank = EstimatorBank(self.estimators) if stream else None

    # -- estimates --------------------------------------------------------
    def current_D(self) -> list[np.ndarray]:
        """The per-server D-matrices the next segment's placements will use.

        With a fleet controller these resolve through the pool map: pooled
        servers share their pool's estimate, split servers their own.
        """
        if self.fleet is not None:
            return self.fleet.current_D()
        return [est.estimate_D() for est in self.estimators]

    def engine_for_segment(self, segment: int) -> ConsolidationEngine:
        """A ConsolidationEngine scoring with estimates over the true world.

        Engines are cached across segments: while the specs are unchanged
        only the estimated D moves, and ``set_D`` swaps it without rebuilding
        the ground-truth dynamics (or re-tracing the engine's jit programs).
        When drift changes the specs, the new engine still reuses any
        previously built ``PackedDynamics`` for that world (drift schedules
        revisit worlds: congest -> recover)."""
        specs = (tuple(self.drift.specs_at(self.servers, segment))
                 if self.drift is not None else self.servers)
        mask = self.fleet.active_mask() if self.fleet is not None else None
        key = (specs, None if mask is None else mask.tobytes())
        engine = self._engine_cache.get(key)
        if engine is not None:
            engine.set_D(self.current_D(), active=mask)
            return engine
        engine = ConsolidationEngine(
            list(specs), D=self.current_D(), alpha=self.alpha,
            objective=self.objective, backend="jax", scorer=self.scorer,
            active=mask)
        if specs in self._dyn_cache:
            engine._dyn = self._dyn_cache[specs]
        else:
            self._dyn_cache[specs] = engine.dyn  # builds the tables once
        self._engine_cache[key] = engine
        return engine

    def _decision_ring(self) -> DecisionRing:
        """The recorder's host mirror, minted on first use."""
        if self.decisions is None:
            self.decisions = DecisionRing(self.decision_capacity)
        return self.decisions

    def _recorder_ctx(self, segment: int) -> "obs_recorder.RecCtx":
        """Per-segment recorder context from the live host-side state --
        what the *next* engine dispatch's scheduler will consult."""
        if self.fleet is not None:
            # stamp with the controller's live burn-in clock -- the device
            # loop stamps carry.seen, which starts at _segments_seen
            return self.fleet.recorder_ctx(self.fleet._segments_seen)
        m = len(self.servers)
        if self.bank is not None:
            n_pair = self.bank.stacked_state().n_pair_t
        else:
            n_pair = jnp.asarray(
                np.stack([np.asarray(e.n_pair).T for e in self.estimators]),
                jnp.float32)
        ident = jnp.arange(m, dtype=jnp.int32)
        return obs_recorder.RecCtx(
            n_pair=n_pair, row_of=ident,
            cusum=jnp.zeros((m,), jnp.float32),  # no detector in the loop
            pool_row=ident, segment=jnp.int32(segment))

    # -- the loop ---------------------------------------------------------
    def run(
        self,
        arrivals: Sequence[tuple[float, Workload]],
        segments: int = 8,
        on_segment: Callable[[int, EngineResult, "AdaptiveEngine"], None] | None = None,
        *,
        device_loop: bool = False,
        metrics: bool = False,
        record: bool = False,
    ) -> AdaptiveResult:
        """Alternate ``segments`` trace chunks with estimator refreshes.

        ``on_segment(k, result, self)`` fires after each segment's
        observations have been folded in (and, with a fleet controller,
        after its health actions for the segment) -- benchmarks use it to
        snapshot estimation error and regret as observation volume grows.

        With a fleet controller, an eviction requeues the evicted server's
        in-flight work: the detection segment's arrivals that ran on the
        evicted server (their observed service came from a collapsing
        machine), plus any never-placed arrivals, re-enter at the head of
        the next segment's chunk. An eviction fired by the *final* segment
        has no next chunk; its in-flight work stays reported in that
        segment's result.

        ``device_loop=True`` compiles the whole multi-segment cycle into
        one device program (``core.closed_loop``) instead of alternating
        host and device per segment -- same decisions, same final state, a
        fraction of the dispatch overhead. It requires stream mode, an
        arrival count divisible by ``segments``, structure-preserving drift
        (``llc_bytes``/``llc_tolerance`` fixed), and no ``on_segment``
        callback (there is no host between segments to call it from); this
        host-alternating path remains the reference oracle (DESIGN.md
        section 13).

        ``metrics=True`` threads the ``repro.obs`` MetricFrame through every
        segment and attaches the merged run frame as ``result.metrics``; the
        split/evict/requeue counters bit-match ``result.health`` on both
        paths. On the device loop the frame rides the scan carry; here it is
        merged per segment on the host -- same decision-level counters, with
        the device-only extras noted on :class:`AdaptiveResult`.

        ``record=True`` threads the decision flight recorder through every
        segment's event loop (``obs.recorder``): one provenance row per
        placement, sampling the estimator pair-exposure / detector CUSUM
        state the segment's scheduler consulted, accumulated into one ring
        (``self.decisions``, capacity ``decision_capacity``) across segments
        and returned on ``result.decisions``. Decisions are unchanged; on
        the device loop the ring rides the scan carry.
        """
        if device_loop:
            if on_segment is not None:
                raise ValueError(
                    "device_loop=True runs all segments in one compiled "
                    "program; there is no per-segment host point for "
                    "on_segment -- use the host-alternating path")
            return self._run_device_loop(arrivals, segments, metrics=metrics,
                                         record=record)
        m = len(self.servers)
        frame = obs_metrics.zeros(m) if metrics else None
        ring = self._decision_ring() if record else None
        ordered = sorted(arrivals, key=lambda tw: tw[0])
        bounds = np.linspace(0, len(ordered), segments + 1).astype(int)
        results, n_obs, t_starts, health = [], [], [], []
        requeue: list[Workload] = []
        for k in range(segments):
            chunk = ordered[bounds[k]:bounds[k + 1]]
            if requeue:
                t0 = chunk[0][0] if chunk else 0.0
                chunk = [(t0, w) for w in requeue] + chunk
                requeue = []
            engine = self.engine_for_segment(k)
            rec_kw = (dict(record=True, rec=ring.state,
                           rec_ctx=self._recorder_ctx(k))
                      if record else {})
            events: "tuple[HealthEvent, ...]" = ()
            if self.stream:
                # fleet-scale path: the segment's rows go trace -> ring ->
                # one banked estimator update without leaving the device
                res = engine.run(chunk, telemetry="device", metrics=metrics,
                                 **rec_kw)
                used = 0
                if res.stream_block is not None:
                    # estimators consume the segment's FULL block; the ring
                    # (which keeps only its newest capacity rows) is the
                    # bounded history for re-reads, not the update source
                    self.ring.push(res.stream_block)
                    if self.fleet is not None:
                        used, evs = self.fleet.observe(res.stream_block, segment=k)
                        events = tuple(evs)
                        evicted = {ev.server for ev in evs if ev.kind == "evict"}
                        if evicted:
                            requeue = [w for (t, w), p in
                                       zip(chunk, res.placements)
                                       if p in evicted or p is None]
                    else:
                        used = self.bank.update_device(res.stream_block)
            else:
                res = engine.run(chunk, telemetry=True, metrics=metrics,
                                 **rec_kw)
                used = sum(est.update(res.observations.for_server(s))
                           for s, est in enumerate(self.estimators))
            if record and res.decisions is not None:
                ring.adopt(res.decisions)  # the next segment continues it
            if metrics:
                # the same closed-loop accounting the device scan keeps in
                # its carry, from the host's own bookkeeping
                frame = obs_metrics.merge(frame, res.metrics)
                frame = obs_metrics.count(frame, "segments", 1)
                frame = obs_metrics.count(
                    frame, "splits",
                    sum(1 for ev in events if ev.kind == "split"))
                frame = obs_metrics.count(
                    frame, "evictions",
                    sum(1 for ev in events if ev.kind == "evict"))
                frame = obs_metrics.count(frame, "requeues", len(requeue))
                frame = obs_metrics.gauge_max(
                    frame, "requeue_peak", float(len(requeue)))
                if self.stream:
                    frame = obs_metrics.count(frame, "ring_rows", len(chunk))
                    frame = obs_metrics.gauge_max(
                        frame, "ring_occupancy_peak",
                        float(min(self.ring.total, self.ring.capacity)))
                if self.fleet is not None:
                    frame = obs_metrics.gauge_max(
                        frame, "evicted_peak",
                        float((~self.fleet.active_mask()).sum()))
            results.append(res)
            n_obs.append(used)
            t_starts.append(chunk[0][0] if chunk else 0.0)
            health.append(events)
            if on_segment is not None:
                on_segment(k, res, self)
        return AdaptiveResult(tuple(results), tuple(n_obs), tuple(t_starts),
                              tuple(health), metrics=frame, decisions=ring)

    # -- the fused device-resident loop -----------------------------------
    def _pack_device_loop(
        self, arrivals: Sequence[tuple[float, Workload]], segments: int,
        *, metrics: bool = False, record: bool = False,
    ) -> "DeviceLoopInputs":
        """The device loop's prologue (the ``closed_loop.pack`` span):
        validate the run, pack arrivals and dynamics, and snapshot the live
        estimator/detector/pool state into the scan carry -- everything
        one ``run_closed_loop`` call consumes.

        Per engine, built on the first run that needs them and reused
        (``closed_loop.pack.tables``, attribute ``cached``; counted in
        ``loop_tables_stats``):

        * ``cluster`` and ``dyn_stack``, keyed by the ordered tuple of
          distinct per-segment worlds and ``alpha``. They are functions of
          that key alone: the cluster's structural tables come from the
          servers' LLC sizes (which every world must share) and ``alpha``,
          its ``D``/``active`` are replaced inside the program from the
          carry, so evictions need no new entry; the dynamics are the
          worlds' tables. A drift schedule indexes worlds by segment, so a
          repeated run repeats its key.
        * ``Lp_t`` and ``logb_priors``: the estimators' priors, fixed at
          their construction.

        ``run_closed_loop`` donates none of its inputs, so a cached array
        survives every call. At 1,024 servers the cluster's and the priors'
        ``[m, T, T]`` tables are ~217 MB each, held for the engine's life:
        the memory each run allocated anew before, so peak use does not
        rise (one more cluster and dynamics stack per further key).

        Per run: the arrivals, the structural-drift check and ``dyn_idx``
        (cheap host numpy), and the carry, whose state moves every run."""
        from ..fleet.detect import CusumState
        from .closed_loop import ClosedLoopConfig, LoopCarry, SegmentIn

        if not self.stream:
            raise ValueError("device_loop=True requires stream mode "
                             "(stream=True or a fleet controller)")
        n = len(arrivals)
        if n == 0 or segments <= 0 or n % segments != 0:
            raise ValueError(
                f"device_loop=True needs a non-empty arrival trace divisible "
                f"by segments (got {n} arrivals / {segments} segments); the "
                f"host-alternating path handles ragged chunks")
        m = len(self.servers)
        n_seg = n // segments
        R = n_seg  # requeue capacity: one segment's worth of in-flight work
        if R + n_seg > self.ring.capacity:
            raise ValueError(
                f"segment size {n_seg} (+{R} requeue slots) exceeds the "
                f"telemetry ring capacity {self.ring.capacity}")
        e0 = self.estimators[0]
        if any(e.confidence_floor != e0.confidence_floor
               for e in self.estimators):
            raise ValueError("device_loop=True blends every row's D with one "
                             "confidence_floor; estimators disagree")

        with obs_trace.span("closed_loop.pack", segments=segments, m=m):
            with obs_trace.span("closed_loop.pack.arrivals"):
                ordered = sorted(arrivals, key=lambda tw: tw[0])
                times = np.asarray([t for t, _ in ordered], np.float64)
                wtypes = np.asarray([type_index(w) for _, w in ordered], np.int32)
                nbytes = np.asarray([w.data_total for _, w in ordered], np.float64)

                # segments bucket to a power-of-two count (padding masked by
                # seg_valid) so warm runs across different segment counts of the
                # same fleet hit one compilation
                S_cap = 4
                while S_cap < segments:
                    S_cap *= 2
                arr_time = np.zeros((S_cap, n_seg), np.float32)
                arr_type = np.zeros((S_cap, n_seg), np.int32)
                arr_bytes = np.ones((S_cap, n_seg), np.float32)
                t0s = []
                for k in range(segments):
                    sl = slice(k * n_seg, (k + 1) * n_seg)
                    t0 = float(times[k * n_seg])
                    t0s.append(t0)
                    arr_time[k] = times[sl] - t0
                    arr_type[k] = wtypes[sl]
                    arr_bytes[k] = nbytes[sl]

            with obs_trace.span("closed_loop.pack.tables") as tables_span:
                # per-segment worlds, deduplicated into one stacked dynamics bank;
                # the compiled cluster's structural tables must hold for all of them
                structural = [(s.llc_bytes, s.llc_tolerance) for s in self.servers]
                spec_of: dict[tuple[ServerSpec, ...], int] = {}
                dyn_idx = np.zeros(S_cap, np.int32)
                for k in range(segments):
                    specs = (tuple(self.drift.specs_at(self.servers, k))
                             if self.drift is not None else self.servers)
                    if [(s.llc_bytes, s.llc_tolerance) for s in specs] != structural:
                        raise ValueError(
                            "device_loop=True compiles one cluster for all segments: "
                            "drift may not change llc_bytes/llc_tolerance (run the "
                            "host-alternating path for structural drift)")
                    dyn_idx[k] = spec_of.setdefault(specs, len(spec_of))
                alpha = self.alpha
                key = (tuple(spec_of), alpha if isinstance(alpha, (int, float))
                       else tuple(alpha))
                tables = self._loop_tables.get(key)
                tables_span["cached"] = tables is not None
                if tables is None:
                    self.loop_tables_stats["misses"] += 1
                    for specs in spec_of:
                        if specs not in self._dyn_cache:
                            self._dyn_cache[specs] = PackedDynamics.build(list(specs))
                    dyn_stack = jax.tree_util.tree_map(
                        lambda *a: jnp.stack(a),
                        *(self._dyn_cache[s] for s in spec_of))
                    cluster = PackedCluster.build(
                        list(self.servers),
                        [np.zeros((GRID_T, GRID_T), np.float32)] * m, alpha)
                    tables = self._loop_tables[key] = (cluster, dyn_stack)
                else:
                    self.loop_tables_stats["hits"] += 1
                cluster, dyn_stack = tables

                if self._loop_priors is None:
                    self._loop_priors = (
                        jnp.asarray(np.stack([e._L_prior.T for e in self.estimators]),
                                    jnp.float32),
                        jnp.asarray(np.stack([e._logb_prior for e in self.estimators]),
                                    jnp.float32))
                Lp_t, logb_priors = self._loop_priors

            with obs_trace.span("closed_loop.pack.state"):
                scorer = None if self.scorer == "jnp" else make_scorer(self.scorer)
                h = e0._hypers
                est_h = dict(
                    lr=h["lr"], decay=h["decay"], step_damp=h["step_damp"],
                    solo_eps=h["solo_eps"], est_max_lost_frac=h["max_lost_frac"],
                    use_pallas=h["use_pallas"])
                frame0 = obs_metrics.zeros(m) if metrics else None
                rec0 = self._decision_ring().state if record else None
                fc = self.fleet
                if fc is not None:
                    fc._require_bound()
                    config = ClosedLoopConfig(
                        objective=self.objective, scorer=scorer, fleet=True,
                        warmup_segments=fc.warmup_segments, cusum_k=fc.cusum_k,
                        cusum_h=fc.cusum_h, level_decay=fc.level_decay,
                        fail_floor=fc.fail_floor, min_exposure=fc.min_exposure,
                        det_max_lost_frac=fc.max_lost_frac,
                        confidence_floor=float(e0.confidence_floor),
                        metrics=metrics, record=record, **est_h)
                    carry0 = LoopCarry(
                        bank=fc.pool.bank.stacked_state(), det=fc.detector.state,
                        row_map=jnp.asarray(fc.pool.row_of, jnp.int32),
                        read_row=jnp.asarray(fc.pool._read_row, jnp.int32),
                        active=jnp.asarray(fc._active),
                        seen=jnp.int32(fc._segments_seen),
                        req_type=jnp.zeros((R,), jnp.int32),
                        req_bytes=jnp.ones((R,), jnp.float32),
                        req_n=jnp.int32(0),
                        ring=self.ring._buf, ring_ptr=jnp.int32(self.ring.ptr),
                        ring_total=jnp.int32(self.ring.total),
                        metrics=frame0, rec=rec0)
                else:
                    config = ClosedLoopConfig(
                        objective=self.objective, scorer=scorer, fleet=False,
                        confidence_floor=float(e0.confidence_floor),
                        metrics=metrics, record=record, **est_h)
                    carry0 = LoopCarry(
                        bank=self.bank.stacked_state(), det=CusumState.zeros(m),
                        row_map=jnp.arange(m, dtype=jnp.int32),
                        read_row=jnp.arange(m, dtype=jnp.int32),
                        active=jnp.ones(m, bool), seen=jnp.int32(0),
                        req_type=jnp.zeros((R,), jnp.int32),
                        req_bytes=jnp.ones((R,), jnp.float32),
                        req_n=jnp.int32(0),
                        ring=self.ring._buf, ring_ptr=jnp.int32(self.ring.ptr),
                        ring_total=jnp.int32(self.ring.total),
                        metrics=frame0, rec=rec0)
                xs = SegmentIn(
                    arr_time=jnp.asarray(arr_time), arr_type=jnp.asarray(arr_type),
                    arr_bytes=jnp.asarray(arr_bytes), dyn_idx=jnp.asarray(dyn_idx),
                    seg_valid=jnp.asarray(np.arange(S_cap) < segments))
        return DeviceLoopInputs(cluster, dyn_stack, Lp_t, logb_priors, carry0,
                                xs, config, tuple(t0s))

    def _run_device_loop(
        self, arrivals: Sequence[tuple[float, Workload]], segments: int,
        *, metrics: bool = False, record: bool = False,
    ) -> AdaptiveResult:
        """One ``run_closed_loop`` dispatch for the whole multi-segment run.

        Host work is strictly prologue (:meth:`_pack_device_loop`) and
        epilogue (unpack per-segment results, mirror the final carry back
        into the host objects via ``FleetController.adopt_device_outcome`` /
        ``PooledEstimatorBank.adopt_rows``). Per-segment ``EngineResult``s
        carry no ``observations``/``stream_block``: the telemetry was
        consumed inside the program (the ring holds the bounded history).

        The three host phases are wrapped in ``repro.obs.trace`` spans
        (``closed_loop.pack`` / ``.dispatch`` / ``.epilogue``) so profiler
        traces and span logs separate packing and adoption cost from the
        blocking dispatch (which includes compilation on a cold cache).
        With ``metrics=True`` the MetricFrame rides the scan carry and the
        merged run frame is returned on ``AdaptiveResult.metrics``.
        """
        from .closed_loop import run_closed_loop

        packed = self._pack_device_loop(arrivals, segments, metrics=metrics,
                                        record=record)
        m, R = len(self.servers), int(packed.carry.req_type.shape[0])
        t0s, fc = packed.t0s, self.fleet
        with obs_trace.span("closed_loop.dispatch", segments=segments, m=m,
                            s_cap=int(packed.xs.seg_valid.shape[0])):
            with obs_trace.span("closed_loop.dispatch.call"):
                final, ys = run_closed_loop(*packed[:7])
            with obs_trace.span("closed_loop.dispatch.wait"):
                jax.block_until_ready(ys)
            with obs_trace.span("closed_loop.dispatch.fetch"):
                ys = jax.tree_util.tree_map(np.asarray, ys)

        # failures surface before any state is adopted, leaving the host
        # objects where they were (the failed run never happened)
        if ys.deadlock[:segments].any():
            raise RuntimeError(
                "deadlock: queued workloads fit no empty server")
        if ys.req_overflow[:segments].any():
            raise RuntimeError(
                f"eviction requeued more than one segment's worth of work "
                f"({R} slots); run the host-alternating path")

        with obs_trace.span("closed_loop.epilogue", segments=segments):
            results, n_obs = [], []
            for k in range(segments):
                nv = int(ys.n_valid[k])
                t0 = t0s[k]
                placement = ys.placement[k][:nv]
                pt = ys.place_time[k][:nv].astype(np.float64)
                ft = ys.finish_time[k][:nv].astype(np.float64)
                pt = np.where(pt >= 0.0, pt + t0, pt)
                ft = np.where(np.isfinite(ft), ft + t0, ft)
                results.append(EngineResult(
                    placements=tuple(int(p) if p != QUEUED else None
                                     for p in placement),
                    was_queued=tuple(bool(q) for q in ys.was_queued[k][:nv]),
                    place_times=tuple(float(t) for t in pt),
                    finish_times=tuple(float(t) for t in ft),
                    makespan=float(ys.makespan[k]) + t0,
                    max_observed_degradation=float(ys.max_deg[k]),
                    backend="jax"))
                n_obs.append(int(ys.used[k]))

            if fc is not None:
                outcomes = [
                    dict(segment=k, split_fired=ys.split_fired[k],
                         split_stat=ys.split_stat[k],
                         evict_fired=ys.evict_fired[k],
                         evict_stat=ys.evict_stat[k],
                         evict_route=ys.evict_route[k],
                         active_after=ys.active_after[k])
                    for k in range(segments)]
                per_seg = fc.adopt_device_outcome(
                    final.bank, final.det, np.asarray(final.row_map),
                    np.asarray(final.read_row), np.asarray(final.active),
                    outcomes)
                health = [tuple(evs) for evs in per_seg]
            else:
                self.bank._stacked = final.bank
                self.bank._dirty = True
                health = [() for _ in range(segments)]
            self.ring._buf = final.ring
            self.ring.ptr = int(final.ring_ptr)
            self.ring.total = int(final.ring_total)
            if record:
                self.decisions.adopt(final.rec)
        return AdaptiveResult(tuple(results), tuple(n_obs), tuple(t0s),
                              tuple(health), metrics=final.metrics,
                              decisions=self.decisions if record else None)
