"""Host-side structured spans around the device-resident programs.

The hot path itself is one XLA program; its layers are told apart on the
device by ``jax.named_scope`` (``obs.*`` op-name prefixes). What is
host-visible, and what dominates interactive latency, are the phases around
it: packing segment buffers, the blocking dispatch (compile on a cold cache,
execute on a warm one), and the epilogue that adopts device outcomes back
into host bookkeeping. :func:`span` wraps those phases with

  * ``jax.profiler.TraceAnnotation`` -- so profiler traces are navigable by
    phase name, and
  * an in-memory :class:`SpanLog` when tracing is enabled.

Each logged span is stamped on ``time.time_ns()``, the clock the profiler
stamps its host events with, so a span and its annotation in an
``.xplane.pb`` mark the same interval and spans outside a profiler slice can
still be laid on the slice's timeline.

Tracing is off by default; :func:`span` then degrades to a bare profiler
annotation (nanoseconds when no profiler is attached).
"""
from __future__ import annotations

import contextlib
import dataclasses
import time

import jax


@dataclasses.dataclass
class Span:
    name: str
    start_ns: int  # time.time_ns() at open
    end_ns: int  # time.time_ns() at close
    attrs: dict
    #: stable per-log id, assigned at span *open* so parents number before
    #: their children even though children close (and append) first
    id: int = 0
    #: id of the enclosing open span, None for top-level phases
    parent: "int | None" = None
    #: nesting depth (0 = top level)
    depth: int = 0

    @property
    def duration_s(self) -> float:
        return (self.end_ns - self.start_ns) * 1e-9


class SpanLog:
    """Collects spans in memory.

    Nested :meth:`span` calls are linked: each span records the ``id`` of
    the span that was open when it started (``parent``) and its nesting
    ``depth``, so a dispatch phase's call / wait / fetch inside the outer
    dispatch span renders as a tree rather than a flat list
    (:func:`repro.obs.report.phase_tree`).
    """

    def __init__(self):
        self.spans: "list[Span]" = []
        self._next_id = 0
        self._open: "list[int]" = []  # ids of currently open spans

    @contextlib.contextmanager
    def span(self, name: str, **attrs):
        """Log one span; yields its ``attrs``, which the phase may extend
        with what it learns inside (a cache outcome, say)."""
        sid = self._next_id
        self._next_id += 1
        parent = self._open[-1] if self._open else None
        depth = len(self._open)
        self._open.append(sid)
        try:
            with jax.profiler.TraceAnnotation(name):
                t0 = time.time_ns()
                yield attrs
                t1 = time.time_ns()
        finally:
            self._open.pop()
        self.spans.append(Span(name, t0, t1, attrs, id=sid, parent=parent,
                               depth=depth))

    def durations(self) -> "dict[str, float]":
        """Total seconds per span name."""
        out: dict[str, float] = {}
        for s in self.spans:
            out[s.name] = out.get(s.name, 0.0) + s.duration_s
        return out


_ACTIVE: "SpanLog | None" = None


def enable_tracing() -> SpanLog:
    """Install a process-wide SpanLog."""
    global _ACTIVE
    _ACTIVE = SpanLog()
    return _ACTIVE


def disable_tracing() -> None:
    global _ACTIVE
    _ACTIVE = None


def active_log() -> "SpanLog | None":
    """The installed SpanLog, if tracing is enabled."""
    return _ACTIVE


@contextlib.contextmanager
def span(name: str, **attrs):
    """Annotate a host-side phase; logs to the active SpanLog if any.

    Yields the span's attribute dict: ``with span("x") as attrs:
    attrs["hit"] = ...`` records an outcome known only inside the phase
    (discarded when tracing is off)."""
    if _ACTIVE is not None:
        with _ACTIVE.span(name, **attrs) as logged:
            yield logged
    else:
        with jax.profiler.TraceAnnotation(name):
            yield attrs
