"""The trace reduction: busy union, idle gaps and their attribution."""
import time

import jax
import jax.numpy as jnp
import pytest

from bench import tracing
from bench.tracing import Event, TraceData


def test_union_merges_overlaps_and_touching():
    assert tracing.union([(3, 4), (0, 1), (0.5, 2), (2, 2.5)]) == [(0, 2.5), (3, 4)]


def test_gaps_are_the_complement_within_the_window():
    busy = [(1.0, 2.0), (1.5, 3.0), (5.0, 9.0)]
    assert tracing.gaps(tracing.union(busy), 0.0, 6.0) == [(0.0, 1.0), (3.0, 5.0)]
    assert tracing.covered(tracing.union(busy), 0.0, 6.0) == pytest.approx(3.0)


def test_reduce_counts_overlapping_ops_once_and_attributes_gaps():
    ops = [Event("a", 0.0, 1.0), Event("b", 0.5, 1.5), Event("a", 4.0, 5.0)]
    host = [Event("bench.round", 0.0, 3.0), Event("bench.round", 3.0, 6.0),
            Event("closed_loop.dispatch", 0.0, 1.6),
            Event("closed_loop.epilogue", 1.6, 3.0),
            Event("closed_loop.pack", 3.0, 4.0),
            Event("closed_loop.dispatch", 4.0, 5.2)]
    r = tracing.reduce(TraceData({"/device:X:0": ops}, host, {}))
    assert r.window_s == pytest.approx(6.0)
    assert r.busy_s == pytest.approx(2.5)
    assert r.rounds == 2
    assert r.device_ops[0] == ("a", pytest.approx(2.0))
    # gaps 1.5-4.0 (split by nothing: its midpoint lies in the epilogue) and
    # 5.0-6.0 (midpoint in the dispatch span, then the bare round)
    assert r.idle_gaps[0] == ("closed_loop.epilogue", pytest.approx(2.5))
    assert r.idle_gaps[1] == ("harness", pytest.approx(1.0))


def test_gaps_name_the_innermost_sub_span():
    """A gap inside a pack or dispatch sub-span is named for the sub-span,
    and the busy time and window read as without them."""
    ops = [Event("a", 0.0, 0.3), Event("a", 0.6, 1.4), Event("b", 2.9, 3.0)]
    phases = [Event("bench.round", 0.0, 3.0),
              Event("closed_loop.pack", 0.0, 0.5),
              Event("closed_loop.dispatch", 0.5, 2.5),
              Event("closed_loop.epilogue", 2.5, 3.0)]
    subs = [Event("closed_loop.pack.arrivals", 0.0, 0.1),
            Event("closed_loop.pack.tables", 0.1, 0.15),
            Event("closed_loop.pack.state", 0.15, 0.5),
            Event("closed_loop.dispatch.call", 0.5, 0.7),
            Event("closed_loop.dispatch.wait", 0.7, 1.5),
            Event("closed_loop.dispatch.fetch", 1.5, 2.5)]
    assert {e.name for e in phases[1:] + subs} == set(tracing.PROGRAM_SPANS)
    r = tracing.reduce(TraceData({"/device:X:0": ops}, phases + subs, {}))
    bare = tracing.reduce(TraceData({"/device:X:0": ops}, phases, {}))
    assert r.idle_gaps == [("closed_loop.dispatch.fetch", pytest.approx(1.5)),
                           ("closed_loop.pack.state", pytest.approx(0.3))]
    assert bare.idle_gaps == [("closed_loop.dispatch", pytest.approx(1.5)),
                              ("closed_loop.pack", pytest.approx(0.3))]
    assert (r.busy_s, r.window_s, r.rounds, r.device_ops) == \
        (bare.busy_s, bare.window_s, bare.rounds, bare.device_ops)


def test_reduce_finds_nothing_without_device_ops_or_rounds():
    host = [Event("bench.round", 0.0, 1.0)]
    assert tracing.reduce(TraceData({}, host, {})) is None
    assert tracing.reduce(TraceData({"/device:X:0": [Event("a", 0, 1)]}, [], {})) is None


def test_reduction_of_a_trace_recorded_on_the_cpu(tmp_path):
    f = jax.jit(lambda x: jnp.sin(x) @ x)
    x = jnp.ones((384, 384))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    try:
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.round"):
                with jax.profiler.TraceAnnotation("closed_loop.pack"):
                    time.sleep(0.02)
                with jax.profiler.TraceAnnotation("closed_loop.dispatch"):
                    f(x).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    path = next(tmp_path.glob("plugins/profile/*/*.xplane.pb"))
    # on the CPU, XLA's programs run on the PjRt client's host threads
    cpu_ops = lambda plane, lines: [ln for ln in lines if ln.startswith("tf_XLAPjRtCpuClient")] \
        if plane == "/host:CPU" else []
    data = tracing.load_xplane(path, device_lines=cpu_ops)
    r = tracing.reduce(data)
    assert r is not None and r.rounds == 3
    assert 0.0 < r.busy_s < r.window_s
    # three sleeps of 20 ms in the pack span are the longest idle gaps
    packs = [g for name, g in r.idle_gaps if name == "closed_loop.pack"]
    assert len(packs) >= 3 and min(sorted(packs)[-3:]) > 0.015
    assert r.idle_gaps[0][0] == "closed_loop.pack"


def test_a_recorded_trace_keeps_the_sub_spans(tmp_path):
    """``load_xplane`` keeps the sub-spans, so a gap in one is named for it."""
    f = jax.jit(lambda x: jnp.sin(x) @ x)
    x = jnp.ones((256, 256))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    try:
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.round"):
                with jax.profiler.TraceAnnotation("closed_loop.dispatch"):
                    with jax.profiler.TraceAnnotation("closed_loop.dispatch.call"):
                        y = f(x)
                    with jax.profiler.TraceAnnotation("closed_loop.dispatch.wait"):
                        y.block_until_ready()
                    with jax.profiler.TraceAnnotation("closed_loop.dispatch.fetch"):
                        time.sleep(0.02)
    finally:
        jax.profiler.stop_trace()
    path = next(tmp_path.glob("plugins/profile/*/*.xplane.pb"))
    cpu_ops = lambda plane, lines: [ln for ln in lines if ln.startswith("tf_XLAPjRtCpuClient")] \
        if plane == "/host:CPU" else []
    data = tracing.load_xplane(path, device_lines=cpu_ops)
    names = {e.name for e in data.host}
    assert {"closed_loop.dispatch", "closed_loop.dispatch.fetch"} <= names
    r = tracing.reduce(data)
    assert r.idle_gaps[0][0] == "closed_loop.dispatch.fetch"
