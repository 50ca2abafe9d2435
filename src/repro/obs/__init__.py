"""repro.obs: the observability plane (DESIGN.md §14).

Three layers, hot to cold:

``metrics``   fixed-shape metric state (:class:`MetricFrame`) that rides
              *inside* the jitted loops -- counters, high-water gauges,
              log-spaced streaming histograms, a per-server block -- with
              pure ``count/observe/merge`` ops and host-side percentile
              extraction. Enabled per run by a static ``metrics=`` flag on
              the engines; off means the carried slot is ``None`` (an empty
              pytree) and the compiled program is byte-identical.
``trace``     host-side structured spans around the phases that *surround*
              the device programs (pack/dispatch/epilogue and their
              sub-phases), emitted both as ``jax.profiler`` annotations (so
              ``--profile`` traces are navigable) and, with tracing on, as
              an in-memory span log on the profiler's host clock.
``report``    renders a run report (counter/gauge/percentile tables,
              per-server utilization-floor violations, fleet health-event
              timeline) from an ``EngineResult``/``AdaptiveResult``, and
              flattens frames into ``BENCH_*.json`` records.

Two colder layers ride on the same carry mechanism:

``recorder``  the decision flight recorder: a fixed-capacity ring of packed
              per-placement provenance rows (chosen server, top-k candidate
              scores, tie margin, Eqn-4 headroom, queue depth, pair-
              confidence exposure, CUSUM level, pool row) written inside the
              event loop behind a static ``record=`` flag -- recorder-off
              programs stay byte-identical, recorder-on runs stay
              decision-identical.
``explain``   host-side regret attribution over an exported ring: forced
              true-dynamics replays decompose each recorded decision's
              makespan contribution into estimation error / queueing delay /
              detection lag, telescoping exactly to the total regret.

``python -m repro.obs --selfcheck`` exercises the histogram math, the report
path, and the recorder/attribution plane end to end; CI runs it in the
static-analysis job. ``python -m repro.obs --explain`` renders a recorded
run's per-decision timeline and attribution table.
"""
from .metrics import (
    COUNTERS,
    GAUGES,
    HIST_BINS,
    HISTOGRAMS,
    PER_SERVER,
    HistSpec,
    MetricFrame,
    add_server,
    count,
    counter_value,
    gauge_max,
    gauge_set,
    gauge_value,
    hist_counts,
    merge,
    observe,
    percentiles,
    snapshot,
    zeros,
)
from .recorder import KIND_ARRIVE, KIND_DRAIN, KIND_QUEUED, REC_TOPK, DecisionRing, RecCtx, RecState
from .trace import SpanLog, disable_tracing, enable_tracing, span

__all__ = [
    "COUNTERS",
    "GAUGES",
    "HIST_BINS",
    "HISTOGRAMS",
    "KIND_ARRIVE",
    "KIND_DRAIN",
    "KIND_QUEUED",
    "PER_SERVER",
    "REC_TOPK",
    "DecisionRing",
    "HistSpec",
    "MetricFrame",
    "RecCtx",
    "RecState",
    "SpanLog",
    "add_server",
    "count",
    "counter_value",
    "disable_tracing",
    "enable_tracing",
    "gauge_max",
    "gauge_set",
    "gauge_value",
    "hist_counts",
    "merge",
    "observe",
    "percentiles",
    "snapshot",
    "span",
    "zeros",
]
