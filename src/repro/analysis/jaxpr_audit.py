"""Jaxpr-level purity audit of the registered consolidation hot paths.

Every entry point that claims to be device-resident is registered here with a
**purity tier** and a builder that constructs production-shaped example
inputs (T = 230 -- the paper's 10 x 23 grid -- realistic fleet/batch sizes).
The auditor lowers the entry to its ClosedJaxpr (no compilation, no
execution) and walks every equation, recursing through ``jit`` /
``while`` / ``scan`` / ``cond`` sub-jaxprs, checking the tier's contract:

  host-callback      ``pure_callback`` / ``io_callback`` / ``debug_callback``
                     / ``debug_print`` (``jax.debug.print``) anywhere in a device
                     tier: each is a host round-trip in a path that promises
                     zero host syncs.
  float64-leak       a non-weak float64 intermediate on a device tier.
                     Tracing runs under ``enable_x64`` so un-annotated numpy
                     constants surface as f64 instead of being silently
                     downcast by the global x64=off default; *weak*-typed
                     f64 scalars (python literals) are fine -- they never
                     force promotion -- and int64 iota artifacts of the
                     forced flag are ignored.
  dynamic-shape      any abstract value whose shape is not a tuple of
                     concrete ints: the fixed-shape contract every jitted
                     hot path relies on for cache stability.
  donation           declared donation that can never apply: a donated input
                     with no output of matching shape/dtype cannot alias, so
                     the "in-place" ring push would silently copy. On
                     backends that implement donation, XLA's "donated buffer
                     not used" warnings during compilation are promoted to
                     findings too (skipped on CPU, which never donates).
  vmem-budget /      every ``pallas_call`` equation found in the trace:
  grid-divisibility  sum of block bytes (block_shape x dtype) per kernel
                     against the per-platform VMEM budget, and each operand's
                     array dims divisible by its block dims (a silent
                     mis-tile otherwise).

Registering a new hot path is one ``HotEntry`` (DESIGN.md §12): name, tier,
and a zero-argument builder returning ``(fn, args)``. The builders below use
*fake* dynamics tables (random-free, deterministic constants) -- tracing
only consumes shapes and dtypes, so the audit never pays for profiling.
"""
from __future__ import annotations

import dataclasses
import math
import warnings
from typing import Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from . import Finding

from jax.extend.core import ClosedJaxpr as _ClosedJaxpr
from jax.extend.core import Jaxpr as _Jaxpr

#: primitives that are host round-trips by construction
CALLBACK_PRIMITIVES = frozenset(
    {"pure_callback", "io_callback", "debug_callback", "debug_print",
     "callback", "outside_call", "host_callback_call"})

#: per-platform on-chip scratch budget for one Pallas kernel's resident
#: blocks. TPU VMEM is ~16 MiB/core; the budget keeps headroom for compiler
#: spills and semaphores rather than sailing at the physical limit.
VMEM_LIMIT_BYTES = 16 * 2**20
VMEM_HEADROOM = 0.75

# -- purity tiers --------------------------------------------------------------
#: strict device residency: the tier of every hot-loop entry point
TIER_DEVICE = "device"
#: device-resident but f64 allowed (reference/oracle paths lowered on CPU)
TIER_DEVICE_F64 = "device-f64"
#: host orchestration: callbacks allowed; only shape stability is checked
TIER_HOST = "host"

#: relaxations granted by each tier (checks *skipped* for members)
TIER_RELAXATIONS: dict[str, frozenset[str]] = {
    TIER_DEVICE: frozenset(),
    TIER_DEVICE_F64: frozenset({"float64-leak"}),
    TIER_HOST: frozenset({"float64-leak", "host-callback"}),
}


@dataclasses.dataclass(frozen=True)
class HotEntry:
    """One registered hot path: what it promises and how to trace it."""

    name: str
    tier: str
    #: () -> (callable, example_args): the callable is traced (not run) with
    #: the args; keyword config is baked in by the builder via a lambda
    build: Callable[[], tuple[Callable, tuple]]
    #: the entry lowers through ``pl.pallas_call`` (golden-snapshot set)
    pallas: bool = False
    #: the entry declares buffer donation; applicability is verified
    donated: bool = False

    def trace(self) -> "tuple[_ClosedJaxpr, bool]":
        """(closed_jaxpr, x64_traced): the jaxpr all checks walk.

        The float64-leak check wants tracing under ``enable_x64`` -- with
        x64 globally off (the shipping config) every f64 is silently
        downcast at trace time and a leak can never appear in the jaxpr.
        Some entries cannot trace under forced x64 (int32/int64 branch
        mismatches that are artifacts of the flag, not bugs); those fall
        back to the default-config trace, where the f64 check is vacuous
        but every other check is unaffected.
        """
        fn, args = self.build()
        try:
            with jax.experimental.enable_x64():
                return jax.make_jaxpr(fn)(*args), True
        except Exception:
            return jax.make_jaxpr(fn)(*args), False


# -- example-input builders ----------------------------------------------------
# Deterministic, profiling-free: tracing consumes shapes/dtypes only, so the
# dynamics tables are constants with the right layout, at production scale
# (T = 230 everywhere; fleet/batch sizes representative of BENCH tiers).

_T = 230  # len(RS_GRID) * len(FS_GRID): the paper's profiling grid


def _f32(shape, fill=0.0):
    return jnp.full(shape, fill, jnp.float32)


def _servers(m: int):
    import dataclasses as dc

    from ..core.server import M1, M2

    base = [M1, M2]
    return [dc.replace(base[i % 2], name=f"{base[i % 2].name}-{i}")
            for i in range(m)]


def _cluster(m: int):
    from ..core.binpack_jax import PackedCluster

    D = [np.full((_T, _T), 0.05, np.float32) for _ in range(m)]
    return PackedCluster.build(_servers(m), D, alpha=1.3)


def _dynamics(m: int):
    from ..core.engine_jax import PackedDynamics

    logd = _f32((m, _T, _T), math.log1p(-0.05))
    return PackedDynamics(
        solo=_f32((m, _T), 1e6), base_lost=_f32((m, _T), 5e5),
        log_keep=logd, log_lost=logd * 2.0,
        comp_bytes=_f32((m, _T), 1e5), tol_budget=_f32((m,), 1e7))


def _ring_block(B: int, fleet: int):
    from ..telemetry.log import RingBlock

    return RingBlock.build(
        wtype=jnp.arange(B, dtype=jnp.int32) % _T,
        server=jnp.arange(B, dtype=jnp.int32) % fleet,
        duration=_f32((B,), 1.0), y=_f32((B,), -0.1),
        co=_f32((B, _T), 0.01), lost_frac=_f32((B,), 0.0),
        valid=_f32((B,), 1.0))


def _estimator_hypers(use_pallas: bool, interpret: bool) -> dict:
    return dict(lr=0.5, decay=0.997, step_damp=0.5, solo_eps=0.05,
                max_lost_frac=0.5, use_pallas=use_pallas, interpret=interpret)


def _build_run_trace():
    from ..core.engine_jax import run_trace

    m, n = 4, 16
    cluster, dyn = _cluster(m), _dynamics(m)
    arr_time = jnp.cumsum(_f32((n,), 0.5))
    arr_type = jnp.arange(n, dtype=jnp.int32) % _T
    arr_bytes = _f32((n,), 1e6)
    fn = lambda c, d, t, ty, b: run_trace(c, d, t, ty, b, telemetry=True)
    return fn, (cluster, dyn, arr_time, arr_type, arr_bytes)


def _build_update_device():
    from ..telemetry.estimator import DeviceEstimatorState, _update_device

    state = DeviceEstimatorState(
        L_t=_f32((_T, _T)), log_b=_f32((_T,)), n_pair_t=_f32((_T, _T)),
        n_base=_f32((_T,)), n_obs=jnp.int32(0))
    block = _ring_block(B=128, fleet=1)
    hypers = _estimator_hypers(use_pallas=True, interpret=False)
    fn = lambda st, blk, srv: _update_device(st, blk, srv, **hypers)
    return fn, (state, block, jnp.int32(-1))


def _build_update_bank():
    from ..telemetry.estimator import DeviceEstimatorState, _update_bank

    m = 4
    state = DeviceEstimatorState(
        L_t=_f32((m, _T, _T)), log_b=_f32((m, _T)), n_pair_t=_f32((m, _T, _T)),
        n_base=_f32((m, _T)), n_obs=jnp.zeros((m,), jnp.int32))
    block = _ring_block(B=128, fleet=m)
    hypers = _estimator_hypers(use_pallas=False, interpret=False)
    fn = lambda st, blk: _update_bank(st, blk, **hypers)
    return fn, (state, block)


def _build_cusum_update():
    from ..fleet.detect import CusumState, _cusum_update

    m, rows, B = 4, 4, 128
    state = CusumState(
        stat=_f32((m, 2)), level=_f32((m,)), n=_f32((m,)),
        pool_level=_f32((rows,)), pool_n=_f32((rows,)))
    block = _ring_block(B=B, fleet=m)
    log_b, L_t = _f32((rows, _T)), _f32((rows, _T, _T))
    row_map = jnp.arange(m, dtype=jnp.int32) % rows
    fn = lambda st, blk, lb, lt, rm: _cusum_update(
        st, blk, lb, lt, rm, k=0.25, level_decay=0.9, max_lost_frac=0.5)
    return fn, (state, block, log_b, L_t, row_map)


def _build_ring_push():
    from ..core.engine_jax import EngineTrace
    from ..telemetry.log import RingBlock, _ring_write_trace

    n, cap = 64, 256
    buf = RingBlock(
        ints=jnp.full((cap, 2), -1, jnp.int32),
        scalars=jnp.zeros((cap, 6), jnp.float32),
        co=jnp.zeros((cap, _T), jnp.float32))
    trace = EngineTrace(
        placement=jnp.zeros((n,), jnp.int32),
        was_queued=jnp.zeros((n,), bool),
        place_time=_f32((n,), 0.0), finish_time=_f32((n,), 1.0),
        makespan=jnp.float32(1.0), max_deg=jnp.float32(0.0),
        deadlock=jnp.asarray(False),
        obs_co=_f32((n, _T), 0.01), obs_lost=_f32((n,), 0.0),
        obs_logr=_f32((n,), -0.1))
    arr_type = jnp.arange(n, dtype=jnp.int32) % _T
    fn = lambda b, tr, ty, p: _ring_write_trace(b, tr, ty, p, 1e-12)
    return fn, (buf, trace, arr_type, jnp.int32(0))


def _build_closed_loop():
    from ..core.closed_loop import (
        ClosedLoopConfig,
        LoopCarry,
        SegmentIn,
        run_closed_loop,
    )
    from ..fleet.detect import CusumState
    from ..telemetry.estimator import DeviceEstimatorState
    from ..telemetry.log import RingBlock

    m, n_seg, S_cap, cap = 4, 4, 4, 256
    R = n_seg  # requeue capacity: one segment's worth, as the engine packs it
    cluster = _cluster(m)
    dyn_stack = jax.tree_util.tree_map(lambda a: a[None], _dynamics(m))
    bank = DeviceEstimatorState(
        L_t=_f32((m, _T, _T)), log_b=_f32((m, _T)),
        n_pair_t=_f32((m, _T, _T)), n_base=_f32((m, _T)),
        n_obs=jnp.zeros((m,), jnp.int32))
    ring = RingBlock(
        ints=jnp.full((cap, 2), -1, jnp.int32),
        scalars=jnp.zeros((cap, 6), jnp.float32),
        co=jnp.zeros((cap, _T), jnp.float32))
    carry = LoopCarry(
        bank=bank, det=CusumState.zeros(m),
        row_map=jnp.arange(m, dtype=jnp.int32),
        read_row=jnp.arange(m, dtype=jnp.int32),
        active=jnp.ones((m,), bool), seen=jnp.int32(0),
        req_type=jnp.zeros((R,), jnp.int32),
        req_bytes=jnp.ones((R,), jnp.float32), req_n=jnp.int32(0),
        ring=ring, ring_ptr=jnp.int32(0), ring_total=jnp.int32(0))
    xs = SegmentIn(
        arr_time=_f32((S_cap, n_seg), 0.5),
        arr_type=jnp.tile(jnp.arange(n_seg, dtype=jnp.int32) % _T, (S_cap, 1)),
        arr_bytes=_f32((S_cap, n_seg), 1e6),
        dyn_idx=jnp.zeros((S_cap,), jnp.int32),
        seg_valid=jnp.ones((S_cap,), bool))
    Lp_t, logb = _f32((m, _T, _T)), _f32((m, _T))
    config = ClosedLoopConfig(fleet=True)
    fn = lambda c, d, lp, lb, cr, x: run_closed_loop(c, d, lp, lb, cr, x, config)
    return fn, (cluster, dyn_stack, Lp_t, logb, carry, xs)


def _build_run_trace_metrics():
    """The metrics-instrumented event loop: same shapes as the plain entry,
    but with the in-carry MetricFrame threaded through -- the instrumentation
    must satisfy the same device-purity contract as the loop it measures."""
    from ..core.engine_jax import run_trace

    m, n = 4, 16
    cluster, dyn = _cluster(m), _dynamics(m)
    arr_time = jnp.cumsum(_f32((n,), 0.5))
    arr_type = jnp.arange(n, dtype=jnp.int32) % _T
    arr_bytes = _f32((n,), 1e6)
    fn = lambda c, d, t, ty, b: run_trace(
        c, d, t, ty, b, telemetry=True, metrics=True)
    return fn, (cluster, dyn, arr_time, arr_type, arr_bytes)


def _build_closed_loop_metrics():
    """Metrics-instrumented multi-segment loop (fleet + metrics on): the
    merge/count/observe ops in the scan body are part of the hot path when
    the flag is set, so they get their own registry row."""
    from ..core.closed_loop import (
        ClosedLoopConfig,
        LoopCarry,
        SegmentIn,
        run_closed_loop,
    )
    from ..fleet.detect import CusumState
    from ..obs import metrics as obs_metrics
    from ..telemetry.estimator import DeviceEstimatorState
    from ..telemetry.log import RingBlock

    m, n_seg, S_cap, cap = 4, 4, 4, 256
    R = n_seg
    cluster = _cluster(m)
    dyn_stack = jax.tree_util.tree_map(lambda a: a[None], _dynamics(m))
    bank = DeviceEstimatorState(
        L_t=_f32((m, _T, _T)), log_b=_f32((m, _T)),
        n_pair_t=_f32((m, _T, _T)), n_base=_f32((m, _T)),
        n_obs=jnp.zeros((m,), jnp.int32))
    ring = RingBlock(
        ints=jnp.full((cap, 2), -1, jnp.int32),
        scalars=jnp.zeros((cap, 6), jnp.float32),
        co=jnp.zeros((cap, _T), jnp.float32))
    carry = LoopCarry(
        bank=bank, det=CusumState.zeros(m),
        row_map=jnp.arange(m, dtype=jnp.int32),
        read_row=jnp.arange(m, dtype=jnp.int32),
        active=jnp.ones((m,), bool), seen=jnp.int32(0),
        req_type=jnp.zeros((R,), jnp.int32),
        req_bytes=jnp.ones((R,), jnp.float32), req_n=jnp.int32(0),
        ring=ring, ring_ptr=jnp.int32(0), ring_total=jnp.int32(0),
        metrics=obs_metrics.zeros(m))
    xs = SegmentIn(
        arr_time=_f32((S_cap, n_seg), 0.5),
        arr_type=jnp.tile(jnp.arange(n_seg, dtype=jnp.int32) % _T, (S_cap, 1)),
        arr_bytes=_f32((S_cap, n_seg), 1e6),
        dyn_idx=jnp.zeros((S_cap,), jnp.int32),
        seg_valid=jnp.ones((S_cap,), bool))
    Lp_t, logb = _f32((m, _T, _T)), _f32((m, _T))
    config = ClosedLoopConfig(fleet=True, metrics=True)
    fn = lambda c, d, lp, lb, cr, x: run_closed_loop(c, d, lp, lb, cr, x, config)
    return fn, (cluster, dyn_stack, Lp_t, logb, carry, xs)


def _build_run_trace_record():
    """The recorder-instrumented event loop: same shapes as the plain entry,
    with the decision flight recorder's ring threaded through the carry --
    the provenance scatter per event must satisfy the same device-purity
    contract as the loop it records (DESIGN.md section 16)."""
    from ..core.engine_jax import run_trace

    m, n = 4, 16
    cluster, dyn = _cluster(m), _dynamics(m)
    arr_time = jnp.cumsum(_f32((n,), 0.5))
    arr_type = jnp.arange(n, dtype=jnp.int32) % _T
    arr_bytes = _f32((n,), 1e6)
    fn = lambda c, d, t, ty, b: run_trace(
        c, d, t, ty, b, telemetry=True, record=True)
    return fn, (cluster, dyn, arr_time, arr_type, arr_bytes)


def _build_closed_loop_record():
    """Recorder-on multi-segment loop (fleet + record): the ring rides the
    scan carry next to the telemetry ring; the per-decision row writes are
    part of the hot path when the flag is set."""
    from ..core.closed_loop import ClosedLoopConfig, run_closed_loop
    from ..obs import recorder as obs_recorder

    fn_args = _build_closed_loop_metrics()
    carry = fn_args[1][4]._replace(rec=obs_recorder.init(256))
    config = ClosedLoopConfig(fleet=True, metrics=True, record=True)
    fn = lambda c, d, lp, lb, cr, x: run_closed_loop(c, d, lp, lb, cr, x, config)
    return fn, fn_args[1][:4] + (carry,) + fn_args[1][5:]


def _server_axis_1():
    """A 1-device mesh ServerAxis: traces the full shard_map path (size-1
    collectives included) on any host, so the sharded entries stay
    registered and auditable in single-device CI."""
    from ..distributed.server_axis import ServerAxis

    return ServerAxis.over_host_devices(1)


def _build_greedy_sharded():
    """The sharded Q x m candidate scorer: score-local-then-argmax-allreduce
    over the server mesh (collectives allowed at tier device; host
    callbacks are banned here exactly as on the dense entries)."""
    from ..core.binpack_jax import greedy_sequence_sharded

    m, n = 4, 16
    axis = _server_axis_1()
    cluster = _cluster(m)
    counts = _f32((m, _T))
    wtypes = jnp.arange(n, dtype=jnp.int32) % _T
    fn = lambda c, cnt, wt: greedy_sequence_sharded(c, cnt, wt, axis)
    return fn, (cluster, counts, wtypes)


def _build_closed_loop_sharded():
    """The whole multi-segment loop under shard_map (1-device mesh): every
    per-segment collective the 10k-server layout runs, host-callback-free."""
    fn_args = _build_closed_loop()
    from ..core.closed_loop import ClosedLoopConfig, run_closed_loop

    config = ClosedLoopConfig(fleet=True, axis=_server_axis_1())
    fn = lambda c, d, lp, lb, cr, x: run_closed_loop(c, d, lp, lb, cr, x, config)
    return fn, fn_args[1]


def _build_consolidation_scores():
    from ..kernels.consolidation import consolidation_scores

    m, Q = 16, 64
    cluster = _cluster(m)
    counts = _f32((m, _T))
    fs_res = cluster.resident * cluster.fs[None, :]
    wtypes = jnp.arange(Q, dtype=jnp.int32) % _T
    fn = lambda c, D, rs, fr, bud, wt: consolidation_scores(
        c, D, rs, fr, bud, wt, interpret=False)
    return fn, (counts, cluster.D, cluster.rs, fs_res, cluster.llc_budget, wtypes)


def _build_pair_scatter():
    from ..kernels.telemetry import pair_scatter

    B, K = 256, 2
    types = jnp.arange(B, dtype=jnp.int32) % _T
    cbar = _f32((B, _T), 0.01)
    vals = _f32((K, B), 0.5)
    fn = lambda t, c, v: pair_scatter(t, c, v, interpret=False)
    return fn, (types, cbar, vals)


def _build_pallas_scorer():
    from ..core.engine import make_scorer

    m, Q = 16, 64
    cluster = _cluster(m)
    counts = _f32((m, _T))
    wtypes = jnp.arange(Q, dtype=jnp.int32) % _T
    scorer = make_scorer("pallas", interpret=False)
    return scorer, (cluster, counts, wtypes)


# model-serving kernels (the co-tenant workloads the consolidation fleet
# runs): not part of the scheduler's closed loop, but every pallas_call in
# the repo is budget-audited, so they register at the same device tier

def _build_rwkv6_scan():
    from ..kernels.rwkv6_scan import rwkv6_scan

    N, S, dh = 4, 64, 64
    seq = _f32((N, S, dh), 0.1)
    fn = lambda r, k, v, w, u, s0: rwkv6_scan(
        r, k, v, w, u, s0, chunk=32, interpret=False)
    return fn, (seq, seq, seq, _f32((N, S, dh), -0.1), _f32((N, dh), 0.1),
                _f32((N, dh, dh)))


def _build_flash_attention():
    from ..kernels.flash_attention import flash_attention

    N, S, dh = 4, 512, 64
    seq = _f32((N, S, dh), 0.1)
    fn = lambda q, k, v: flash_attention(
        q, k, v, causal=True, block_q=256, block_k=256, interpret=False)
    return fn, (seq, seq, seq)


def _build_mamba_scan():
    from ..kernels.mamba_scan import mamba_scan

    B, S, E, N = 2, 64, 512, 16
    fn = lambda da, dbu, c, h0: mamba_scan(
        da, dbu, c, h0, chunk=64, eblock=512, interpret=False)
    return fn, (_f32((B, S, E, N), 0.9), _f32((B, S, E, N), 0.1),
                _f32((B, S, N), 0.1), _f32((B, E, N)))


#: the registry: every entry point the device-resident closed loop stands on
REGISTRY: tuple[HotEntry, ...] = (
    HotEntry("engine_jax.run_trace", TIER_DEVICE, _build_run_trace),
    HotEntry("telemetry.estimator.update_device", TIER_DEVICE,
             _build_update_device, pallas=True),
    HotEntry("telemetry.estimator.update_bank", TIER_DEVICE, _build_update_bank),
    HotEntry("fleet.detect.cusum_update", TIER_DEVICE, _build_cusum_update),
    HotEntry("telemetry.log.ring_push", TIER_DEVICE, _build_ring_push,
             donated=True),
    HotEntry("core.closed_loop.run_closed_loop", TIER_DEVICE,
             _build_closed_loop),
    HotEntry("engine_jax.run_trace[metrics]", TIER_DEVICE,
             _build_run_trace_metrics),
    HotEntry("core.closed_loop.run_closed_loop[metrics]", TIER_DEVICE,
             _build_closed_loop_metrics),
    HotEntry("engine_jax.run_trace[record]", TIER_DEVICE,
             _build_run_trace_record),
    HotEntry("core.closed_loop.run_closed_loop[record]", TIER_DEVICE,
             _build_closed_loop_record),
    HotEntry("binpack_jax.greedy_sequence[sharded]", TIER_DEVICE,
             _build_greedy_sharded),
    HotEntry("core.closed_loop.run_closed_loop[sharded]", TIER_DEVICE,
             _build_closed_loop_sharded),
    HotEntry("kernels.consolidation.consolidation_scores", TIER_DEVICE,
             _build_consolidation_scores, pallas=True),
    HotEntry("kernels.telemetry.pair_scatter", TIER_DEVICE, _build_pair_scatter,
             pallas=True),
    HotEntry("engine.make_scorer[pallas]", TIER_DEVICE, _build_pallas_scorer,
             pallas=True),
    HotEntry("kernels.rwkv6_scan.rwkv6_scan", TIER_DEVICE, _build_rwkv6_scan,
             pallas=True),
    HotEntry("kernels.flash_attention.flash_attention", TIER_DEVICE,
             _build_flash_attention, pallas=True),
    HotEntry("kernels.mamba_scan.mamba_scan", TIER_DEVICE, _build_mamba_scan,
             pallas=True),
)

#: repo-relative files whose ``pallas_call`` sites the registry exercises;
#: ``ast_rules`` fails any pallas_call in a file not listed here, so a new
#: kernel cannot land without a registered budget entry (DESIGN.md §12)
PALLAS_COVERAGE = frozenset({
    "src/repro/kernels/telemetry.py",
    "src/repro/kernels/consolidation.py",
    "src/repro/kernels/rwkv6_scan.py",
    "src/repro/kernels/flash_attention.py",
    "src/repro/kernels/mamba_scan.py",
})


def get_entry(name: str) -> HotEntry:
    for e in REGISTRY:
        if e.name == name:
            return e
    raise KeyError(f"no registered hot entry {name!r}")


# -- the walker ----------------------------------------------------------------

def _sub_jaxprs(eqn):
    for v in eqn.params.values():
        if isinstance(v, _ClosedJaxpr):
            yield v.jaxpr
        elif isinstance(v, _Jaxpr):
            yield v
        elif isinstance(v, (tuple, list)):
            for x in v:
                if isinstance(x, _ClosedJaxpr):
                    yield x.jaxpr
                elif isinstance(x, _Jaxpr):
                    yield x


def iter_eqns(jaxpr):
    """Every equation of ``jaxpr``, recursing into sub-jaxprs (jit, control
    flow, pallas kernel bodies -- anything carrying a jaxpr in its params)."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in _sub_jaxprs(eqn):
            yield from iter_eqns(sub)


def primitive_counts(jaxpr) -> dict[str, int]:
    """Histogram of primitive names over the whole (recursive) jaxpr -- the
    golden-snapshot quantity: a changed count means the lowering changed."""
    counts: dict[str, int] = {}
    for eqn in iter_eqns(jaxpr):
        counts[eqn.primitive.name] = counts.get(eqn.primitive.name, 0) + 1
    return dict(sorted(counts.items()))


def _avals_of(eqn):
    for v in list(eqn.invars) + list(eqn.outvars):
        aval = getattr(v, "aval", None)
        if aval is not None and hasattr(aval, "shape"):
            yield v, aval


def _check_eqns(entry: HotEntry, closed) -> list[Finding]:
    relaxed = TIER_RELAXATIONS[entry.tier]
    findings: list[Finding] = []
    seen: set[tuple[str, str]] = set()  # dedupe (rule, detail) per entry

    def add(rule: str, detail: str):
        if rule in relaxed or (rule, detail) in seen:
            return
        seen.add((rule, detail))
        findings.append(Finding("jaxpr", rule, entry.name, detail))

    for eqn in iter_eqns(closed.jaxpr):
        name = eqn.primitive.name
        if name in CALLBACK_PRIMITIVES:
            cb = eqn.params.get("callback", "")
            add("host-callback", f"primitive '{name}' ({cb})"[:160])
        for v, aval in _avals_of(eqn):
            dtype = getattr(aval, "dtype", None)
            if (dtype is not None and dtype in (jnp.float64, jnp.complex128)
                    and not getattr(aval, "weak_type", False)):
                add("float64-leak", f"{dtype} value in '{name}'")
            if not all(isinstance(d, (int, np.integer)) for d in aval.shape):
                add("dynamic-shape", f"shape {aval.shape} in '{name}'")
    return findings


# -- donation ------------------------------------------------------------------

def _check_donation(entry: HotEntry, closed) -> list[Finding]:
    """Donation declared on a jit whose outputs can never absorb the buffer.

    A donated input aliases an output only when some output matches its
    shape/dtype; a donated invar with no match is a contract violation (the
    'in-place' update silently copies). Purely structural, so it runs on any
    backend -- the XLA runtime warning promotion complements it on devices
    that actually implement donation (``runtime_donation_findings``).
    """
    findings: list[Finding] = []
    for eqn in iter_eqns(closed.jaxpr):
        donated = eqn.params.get("donated_invars")
        if not donated or not any(donated):
            continue
        inner = eqn.params.get("jaxpr")
        jx = inner.jaxpr if isinstance(inner, _ClosedJaxpr) else inner
        if jx is None:  # pragma: no cover
            continue
        outs = [(tuple(v.aval.shape), str(v.aval.dtype)) for v in jx.outvars]
        for dv, var in zip(donated, jx.invars):
            if not dv:
                continue
            sig = (tuple(var.aval.shape), str(var.aval.dtype))
            if sig not in outs:
                findings.append(Finding(
                    "donation", "donation-unapplicable", entry.name,
                    f"donated {sig[1]}{list(sig[0])} has no matching output"))
    if entry.donated and not any(
            any(eqn.params.get("donated_invars") or ())
            for eqn in iter_eqns(closed.jaxpr)):
        findings.append(Finding(
            "donation", "donation-missing", entry.name,
            "entry is registered as donating but no jit declares donation"))
    return findings


def runtime_donation_findings(entry: HotEntry) -> list[Finding]:
    """Promote XLA's "donated buffer not used" warnings to findings.

    Only meaningful where the backend implements donation -- CPU never
    does, so there the check is skipped rather than reporting noise.
    """
    if jax.default_backend() == "cpu":
        return []
    fn, args = entry.build()
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        jax.jit(fn).lower(*args).compile()
    return [
        Finding("donation", "donation-unapplied", entry.name, str(w.message)[:200])
        for w in caught if "donat" in str(w.message).lower()]


# -- pallas VMEM / grid budget -------------------------------------------------

def _block_mappings(eqn):
    gm = eqn.params.get("grid_mapping")
    if gm is None:  # pragma: no cover -- pallas internals moved
        return None, ()
    return gm, getattr(gm, "block_mappings", ())


def _block_size(d) -> "int | None":
    """One BlockSpec dim as an int: ``Blocked(block_size=n)`` (or a bare
    int) gives n; squeezed and other non-blocked dims give None."""
    d = getattr(d, "block_size", d)
    return int(d) if isinstance(d, (int, np.integer)) else None


def pallas_budget_findings(entry: HotEntry, closed) -> tuple[list[Finding], list[dict]]:
    """VMEM residency + grid-divisibility for every pallas_call in the trace.

    The resident-block estimate is the sum over operands of block_shape x
    itemsize -- what the BlockSpecs pin in VMEM simultaneously (double
    buffering and scratch come on top, hence the headroom factor).
    """
    findings: list[Finding] = []
    sites: list[dict] = []
    budget = int(VMEM_LIMIT_BYTES * VMEM_HEADROOM)
    for eqn in iter_eqns(closed.jaxpr):
        if eqn.primitive.name != "pallas_call":
            continue
        gm, mappings = _block_mappings(eqn)
        if gm is None:
            continue
        total = 0
        for bm in mappings:
            aval = bm.array_aval
            block = [_block_size(d) for d in bm.block_shape]
            itemsize = np.dtype(aval.dtype).itemsize
            total += int(np.prod([b or 1 for b in block], dtype=np.int64)) * itemsize
            arr = aval.shape
            for a, b in zip(arr, block):
                if b and a % b:
                    findings.append(Finding(
                        "vmem", "grid-divisibility", entry.name,
                        f"array dim {a} not divisible by block dim {b} "
                        f"(array {list(arr)}, block {block})"))
        sites.append({"entry": entry.name, "grid": list(getattr(gm, "grid", ())),
                      "resident_bytes": total, "budget_bytes": budget})
        if total > budget:
            findings.append(Finding(
                "vmem", "vmem-budget", entry.name,
                f"resident blocks {total / 2**20:.2f} MiB exceed the "
                f"{budget / 2**20:.2f} MiB budget "
                f"({VMEM_HEADROOM:.0%} of {VMEM_LIMIT_BYTES // 2**20} MiB VMEM)"))
    return findings, sites


# -- driver --------------------------------------------------------------------

def audit_entry(entry: HotEntry) -> tuple[list[Finding], dict]:
    """All jaxpr-level checks for one registered entry."""
    closed, x64_traced = entry.trace()
    findings = _check_eqns(entry, closed)
    findings += _check_donation(entry, closed)
    vmem_findings, sites = pallas_budget_findings(entry, closed)
    findings += vmem_findings
    findings += runtime_donation_findings(entry) if entry.donated else []
    info = {"primitives": primitive_counts(closed.jaxpr),
            "pallas_sites": sites, "x64_traced": x64_traced}
    return findings, info


def run_jaxpr_audit(names: "Sequence[str] | None" = None,
                    stats: "dict | None" = None) -> list[Finding]:
    """Audit every registered entry (or the named subset)."""
    findings: list[Finding] = []
    entry_stats: dict[str, dict] = {}
    for entry in REGISTRY:
        if names is not None and entry.name not in names:
            continue
        fs, info = audit_entry(entry)
        findings += fs
        entry_stats[entry.name] = {
            "tier": entry.tier, "findings": len(fs),
            "pallas_sites": info["pallas_sites"],
            "n_primitives": sum(info["primitives"].values()),
            "x64_traced": info["x64_traced"],
        }
    if stats is not None:
        stats["jaxpr"] = entry_stats
    return findings
