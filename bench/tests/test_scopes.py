"""The layer split: device time by innermost op and scope, gaps by sub-span."""
import time

import jax
import jax.numpy as jnp
import pytest

from bench import scopes, tracing
from bench.scopes import Op, Slice
from bench.tracing import Event

LOOP = "jit(run_closed_loop)/while/body/closed_call/obs.segment_event_loop/while/body"


def test_nested_ops_are_charged_to_the_innermost_op():
    ops = [Op(0.0, 10.0, "outer"), Op(2.0, 4.0, "mid"), Op(3.0, 3.5, "inner"),
           Op(12.0, 13.0, "outer")]
    t = scopes.self_times(ops, 0.0, 20.0)
    assert t == pytest.approx({"outer": 9.0, "mid": 1.5, "inner": 0.5})
    # clipped to the window; idle time belongs to nothing
    t = scopes.self_times(ops, 3.25, 12.5)
    assert t == pytest.approx({"outer": 6.5, "mid": 0.5, "inner": 0.25})
    busy = tracing.covered(tracing.union([(o.start, o.end) for o in ops]), 3.25, 12.5)
    assert sum(t.values()) == pytest.approx(busy)


def test_ops_without_a_name_path_take_their_enclosers():
    loop = f"{LOOP[:-len('/while/body')]}/while"
    ops = [Op(0.0, 10.0, loop), Op(1.0, 2.0, ""), Op(1.2, 1.4, ""),
           Op(3.0, 4.0, f"{LOOP}/obs.rates/mul"), Op(11.0, 12.0, "")]
    got = scopes.inherit_paths(ops)
    assert [o.path for o in got] == [loop, loop, loop, f"{LOOP}/obs.rates/mul", ""]
    s = scopes.split(scopes.self_times(got, 0.0, 12.0),
                     {"obs.segment_event_loop", "obs.rates"})
    assert s == pytest.approx({"loop_events_ms": 10.0, "loop_rates_ms": 1.0,
                               "loop_other_ms": 1.0})


def test_nested_scopes_go_to_the_innermost_scope_and_sum_to_busy():
    times = {
        f"{LOOP}/obs.rates/mul": 1.0,
        f"{LOOP}/cond/branch_2_fun/obs.arrive/add": 2.0,
        f"{LOOP}/cond/branch_2_fun/obs.arrive/obs.score/reduce_min": 3.0,
        f"{LOOP}/cond/branch_0_fun/obs.drain/cond/branch_1_fun/obs.score/lt": 0.5,
        f"{LOOP}/cond/branch_0_fun/obs.drain/cumsum": 0.25,
        f"{LOOP}/cond/branch_1_fun/obs.finish/argmax": 0.75,
        "jit(run_closed_loop)/while/body/closed_call/obs.segment_event_loop/while": 4.0,
        "jit(run_closed_loop)/while/body/obs.estimate/scatter-add": 5.0,
        "jit(run_closed_loop)/while/body/obs.detect/fleet_step/max": 6.0,
        "jit(run_closed_loop)/while/body/obs.d_refresh/cond": 7.0,
        "jit(run_closed_loop)/while/body/scatter": 8.0,  # the ring write
        "jit(run_closed_loop)/while": 9.0,  # the scan itself
        "": 10.0,  # an op with no name path
    }
    s = scopes.split(times, {"obs.segment_event_loop", "obs.rates", "obs.arrive",
                             "obs.drain", "obs.finish", "obs.score", "obs.estimate",
                             "obs.detect", "obs.d_refresh"})
    assert s["loop_events_ms"] == pytest.approx(11.5)
    assert s["loop_rates_ms"] == pytest.approx(1.0)
    assert s["loop_arrive_ms"] == pytest.approx(2.0)  # the scorer excluded
    assert s["loop_drain_ms"] == pytest.approx(0.25)
    assert s["loop_finish_ms"] == pytest.approx(0.75)
    assert s["loop_score_ms"] == pytest.approx(3.5)
    assert (s["loop_estimate_ms"], s["loop_detect_ms"], s["loop_d_refresh_ms"]) == (
        5.0, 6.0, 7.0)
    assert s["loop_other_ms"] == pytest.approx(27.0)
    top = sum(s[k] for k in (*scopes.STEP_SCOPES, scopes.OTHER))
    assert top == pytest.approx(sum(times.values()))


def test_reduce_slice_per_round_and_gaps_by_sub_span():
    ops = [Op(0.0, 1.0, f"{LOOP}/obs.rates/mul"),
           Op(0.2, 0.4, f"{LOOP}/obs.rates/obs.score/x"),  # nested inside
           Op(1.0, 1.5, "jit(run_closed_loop)/while"),
           Op(4.0, 5.0, "jit(run_closed_loop)/while/body/obs.estimate/y")]
    host = [Event("bench.round", 0.0, 3.0), Event("bench.round", 3.0, 6.0),
            Event("closed_loop.dispatch", 0.0, 2.9),
            Event("closed_loop.dispatch.call", 0.0, 0.1),
            Event("closed_loop.dispatch.wait", 0.1, 1.5),
            Event("closed_loop.dispatch.fetch", 1.5, 2.9),
            Event("closed_loop.pack", 3.0, 4.0),
            Event("closed_loop.pack.tables", 3.0, 4.0),
            Event("closed_loop.dispatch", 4.0, 6.0),
            Event("closed_loop.dispatch.call", 4.0, 4.05)]
    present = frozenset({"obs.segment_event_loop", "obs.rates", "obs.score",
                         "obs.estimate"})
    r = scopes.reduce_slice(Slice(ops, host, 0, present))
    assert r["rounds"] == 2
    m = r["metrics"]
    assert m["loop_events_ms"] == pytest.approx(1e3 * 1.0 / 2)
    assert m["loop_rates_ms"] == pytest.approx(1e3 * 0.8 / 2)
    assert m["loop_score_ms"] == pytest.approx(1e3 * 0.2 / 2)
    assert m["loop_estimate_ms"] == pytest.approx(1e3 * 1.0 / 2)
    assert m["loop_other_ms"] == pytest.approx(1e3 * 0.5 / 2)
    assert r["busy_ms"] == pytest.approx(1e3 * 2.5 / 2)
    assert "loop_detect_ms" not in m and "loop_arrive_ms" not in m  # not in the program
    assert sum(m.get(k, 0.0) for k in (*scopes.STEP_SCOPES, scopes.OTHER)) == (
        pytest.approx(r["busy_ms"]))
    # gap 1.5-4.0 by its midpoint lies in the first fetch; 5.0-6.0 in the
    # second dispatch only, which no sub-span covers there
    assert r["idle_gaps"] == [["closed_loop.dispatch.fetch", pytest.approx(2.5)],
                              ["closed_loop.dispatch", pytest.approx(1.0)]]
    gap_call = tracing.attribute((4.0, 4.04), host)
    assert gap_call == "closed_loop.dispatch.call"


def test_reduce_slice_finds_nothing_without_name_paths():
    host = [Event("bench.round", 0.0, 1.0)]
    assert scopes.reduce_slice(Slice([Op(0.0, 0.5, "")], host, 0)) is None
    assert scopes.reduce_slice(Slice([], host, 0)) is None
    assert scopes.reduce_slice(Slice([Op(0.0, 0.5, "a/obs.estimate/b")], [], 0)) is None


def test_instruction_of_an_op_event():
    tpu = "%fusion.3 = f32[4]{0:T(128)} fusion(f32[4]{0} %p), kind=kLoop, calls=%c"
    assert scopes._instruction(tpu) == "fusion.3"
    assert scopes._instruction("while.9") == "while.9"
    assert scopes.innermost_scope("a/obs.x/b/obs.y/c") == "obs.y"
    assert scopes.innermost_scope("a/b") is None


def test_span_rows_number_rounds_by_top_level_pack():
    from repro.obs import trace as obs_trace

    log = obs_trace.SpanLog()
    for _ in range(2):
        with log.span("closed_loop.pack"):
            with log.span("closed_loop.pack.arrivals"):
                pass
        with log.span("closed_loop.dispatch"):
            with log.span("closed_loop.dispatch.call"):
                pass
    rows = scopes.span_rows(log.spans)
    assert [(r["name"], r["round"]) for r in rows] == [
        ("closed_loop.pack", 0), ("closed_loop.pack.arrivals", 0),
        ("closed_loop.dispatch", 0), ("closed_loop.dispatch.call", 0),
        ("closed_loop.pack", 1), ("closed_loop.pack.arrivals", 1),
        ("closed_loop.dispatch", 1), ("closed_loop.dispatch.call", 1)]
    assert all(r["start_ns"] > 0 and r["duration_s"] >= 0 for r in rows)


def test_span_rows_of_spans_without_the_shared_clock():
    """A program older than the shared clock stamps ``t_start`` seconds."""
    class OldSpan:
        def __init__(self, i, name, depth):
            self.id, self.name, self.depth = i, name, depth
            self.t_start, self.duration_s = 1.5e9 + i, 0.25

    rows = scopes.span_rows([OldSpan(1, "closed_loop.pack.tables", 1),
                             OldSpan(0, "closed_loop.pack", 0)])
    assert [(r["name"], r["round"], r["start_ns"]) for r in rows] == [
        ("closed_loop.pack", 0, 1_500_000_000_000_000_000),
        ("closed_loop.pack.tables", 0, 1_500_000_001_000_000_000)]


def test_run_args_read_the_harness_command_line(tmp_path):
    a = scopes._run_args(["--workload", "c", "--seed", "7", "--seconds", "3",
                          "--trace", "1", "--out", str(tmp_path)])
    assert (a.workload, a.seed, a.out) == ("c", "7", str(tmp_path))
    a = scopes._run_args(["-q", "-p", "xdist"])
    assert a.workload is None and a.out.endswith(".bench_out")


def test_split_of_a_trace_recorded_on_the_cpu(tmp_path):
    @jax.jit
    def f(x):
        with jax.named_scope("obs.segment_event_loop"):
            def body(c):
                i, y = c
                with jax.named_scope("obs.rates"):
                    y = jnp.sin(y) @ y
                return i + 1, y

            _, y = jax.lax.while_loop(lambda c: c[0] < 3, body, (0, x))
        with jax.named_scope("obs.estimate"):
            return jnp.cos(y) @ y

    x = jnp.ones((256, 256))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    try:
        for _ in range(2):
            with jax.profiler.TraceAnnotation("bench.round"):
                with jax.profiler.TraceAnnotation("closed_loop.dispatch"):
                    with jax.profiler.TraceAnnotation("closed_loop.dispatch.wait"):
                        f(x).block_until_ready()
                    time.sleep(0.005)
    finally:
        jax.profiler.stop_trace()
    path = next(tmp_path.glob("plugins/profile/*/*.xplane.pb"))
    # on the CPU, XLA's programs run on the PjRt client's host threads
    cpu_ops = lambda plane, lines: [ln for ln in lines if ln.startswith("tf_XLAPjRtCpuClient")] \
        if plane == "/host:CPU" else []
    sl = scopes.load(path, device_lines=cpu_ops)
    assert sl.origin_ns is not None
    assert sl.scopes == {"obs.segment_event_loop", "obs.rates", "obs.estimate"}
    names = {ev.name for ev in sl.host}
    assert {"bench.round", "closed_loop.dispatch", "closed_loop.dispatch.wait"} <= names
    r = scopes.reduce_slice(sl)
    m = r["metrics"]
    assert set(m) == {"loop_events_ms", "loop_rates_ms", "loop_estimate_ms", "loop_other_ms"}
    # which thread runs which of XLA's CPU ops varies; some scoped time shows
    assert m["loop_events_ms"] + m["loop_estimate_ms"] > 0
    assert m["loop_rates_ms"] <= m["loop_events_ms"]
    assert m["loop_events_ms"] + m["loop_estimate_ms"] + m["loop_other_ms"] == pytest.approx(
        r["busy_ms"])
    # the 5 ms sleeps after each call are the longest gaps, in the bare dispatch
    assert r["idle_gaps"][0][0] == "closed_loop.dispatch"
