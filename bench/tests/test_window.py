"""Window arithmetic: rate over whole rounds, p95 over all rounds, compiles."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import window


def test_rate_is_all_decisions_over_window_start_to_last_round_end():
    w = window.Window(round_s=[0.1, 0.3, 0.2], end_s=[0.1, 0.45, 0.7],
                      decisions=[10, 10, 12], results=[None] * 3, compiles=0)
    assert w.decisions_per_s() == pytest.approx(32 / 0.7)


def test_p95_is_over_every_round_not_chunk_medians():
    times = [0.01] * 95 + [0.5] * 5
    w = window.Window(round_s=times, end_s=list(np.cumsum(times)),
                      decisions=[1] * 100, results=[None] * 100, compiles=0)
    assert w.round_p95_ms() == pytest.approx(1e3 * np.percentile(times, 95))
    assert w.round_p95_ms() > 10.0  # the slow tail shows


def test_measure_ends_on_the_round_that_crosses_the_window():
    calls = []

    def run(x):
        calls.append(x)
        return x

    clock = iter(np.arange(0.0, 100.0, 0.25))
    import time as _time
    real = _time.perf_counter
    _time.perf_counter = lambda: float(next(clock))
    try:
        w = window.measure(run, list(range(50)), 2.0, window.CompileCounter(),
                           decisions=lambda r: 3)
    finally:
        _time.perf_counter = real
    # each round takes one tick (0.25 s) and the hook-free loop one more
    # between rounds: the last round is the first to end at or past 2.0 s
    assert w.end_s[-1] >= 2.0 and w.end_s[-2] < 2.0
    assert w.rounds == len(calls)
    assert w.decisions_per_s() == pytest.approx(3 * w.rounds / w.end_s[-1])


def test_pool_that_runs_out_is_an_error():
    with pytest.raises(RuntimeError, match="ran out"):
        window.measure(lambda x: x, [1, 2], 60.0, window.CompileCounter(),
                       decisions=lambda r: 1)


def test_compiles_inside_the_window_are_counted():
    counter = window.CompileCounter().install()
    shapes = iter([(3,), (4,), (5,)])
    f = jax.jit(lambda x: x * 2.0)

    def run(_):
        return f(jnp.ones(next(shapes))).block_until_ready()

    w = window.measure(run, [0, 1, 2], 1e-9, counter, decisions=lambda r: 1)
    assert w.rounds == 1 and w.compiles >= 1
    before = counter.count
    f(jnp.ones((3,))).block_until_ready()  # cached: no event
    assert counter.count == before


def test_warm_up_stops_after_two_clean_rounds_in_a_row():
    counter = window.CompileCounter().install()
    g = jax.jit(lambda x: x + 1.0)
    logs = []
    res, clean = window.warm_up(lambda a: g(jnp.ones(a)).block_until_ready(),
                                [(7,), (7,), (8,), (8,), (8,), (8,)], counter,
                                logs.append)
    # a compile at the third round resets the count of clean rounds
    assert len(res) == 5 and len(clean) == 2
    assert "compile" in logs[0]
