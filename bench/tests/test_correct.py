"""The check that decides ``correct``, shown to fail.

Each cell runs end to end on the CPU (the harness's look for a chip
skipped): sound, it comes out correct; with the timed path broken
underneath, once per fault the cell can have, it comes out not correct.
The cells run on one chip, so the fault "the exchange between chips left
out" has no place here.
"""
import json

import jax
import jax.numpy as jnp
import pytest

from bench import run, verify
from bench.generator import Mix
from bench.system import System, decisions, server_list

CELLS = ("paper-4srv.table3",)


def run_cell(cell, tmp_path, capsys, seed=20260000001):
    rc = run.main(["--workload", cell, "--seed", str(seed), "--seconds", "0.5",
                   "--trace", "0", "--out", str(tmp_path)],
                  require_accelerator=False)
    out = capsys.readouterr().out.strip().splitlines()
    assert rc == 0
    return json.loads(out[-1])


def unchanged_state(monkeypatch):
    """The estimator's step returns its state unchanged."""
    import repro.core.closed_loop as cl

    monkeypatch.setattr(cl, "_bank_core", lambda state, block, **kw: (state, jnp.int32(0)))


def half_batch(monkeypatch):
    """Half of each segment's observations left out; the update's per-pair
    means are then taken over the rest."""
    import repro.core.closed_loop as cl

    orig = cl._rows_from_trace

    def rows(trace, a_type):
        blk = orig(trace, a_type)
        keep = jnp.arange(blk.scalars.shape[0]) % 2 == 0
        return blk._replace(
            ints=jnp.where(keep[:, None], blk.ints, -1),
            scalars=blk.scalars.at[:, 3].set(jnp.where(keep, blk.scalars[:, 3], 0.0)))

    monkeypatch.setattr(cl, "_rows_from_trace", rows)


def altered_answer(monkeypatch):
    """Each segment's first placement reported on the next server over,
    after the event loop produced it."""
    import repro.core.closed_loop as cl

    orig = cl._trace_segment

    def trace_segment(cluster, *a, **kw):
        tr = orig(cluster, *a, **kw)
        p = tr.placement
        return tr._replace(placement=p.at[0].set(
            jnp.where(p[0] >= 0, (p[0] + 1) % cluster.m, p[0])))

    monkeypatch.setattr(cl, "_trace_segment", trace_segment)


@pytest.fixture
def fresh_jit():
    jax.clear_caches()
    yield
    jax.clear_caches()


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(cell, tmp_path, capsys, fresh_jit):
    res = run_cell(cell, tmp_path, capsys)
    assert res["correct"] is True, res["checks"]
    assert list(res)[-1] == "checks"
    assert res["failed"] == 0 and res["attempted"] > 0


#: the faults each cell can have: the paper cell learns D from the
#: optimistic zero prior, so a frozen or halved estimator update shows
FAULTS = [(c, f) for c in CELLS for f in (unchanged_state, half_batch, altered_answer)]


@pytest.mark.parametrize("cell,fault", FAULTS,
                         ids=[f"{c}-{f.__name__}" for c, f in FAULTS])
def test_broken_timed_path_is_not_correct(cell, fault, tmp_path, capsys,
                                          monkeypatch, fresh_jit):
    fault(monkeypatch)
    jax.clear_caches()
    res = run_cell(cell, tmp_path, capsys)
    assert res["correct"] is False, (fault.__name__, res["checks"])


@pytest.mark.parametrize("cell", CELLS)
def test_control_in_bfloat16_is_not_correct(cell):
    """The reference in bfloat16, put in the program's place, fails a limit."""
    bench = json.loads((run.ROOT / "BENCHMARK.json").read_text())
    w = next(x for x in bench["workloads"] if x["name"] == cell)
    config = json.loads((run.BENCH / "configs" / f"{w['config']}.json").read_text())
    limits = json.loads((run.BENCH / "limits" / f"{cell}.json").read_text())
    mix = Mix.load(run.BENCH / "traffic" / f"{w['traffic']}.json")
    rounds = mix.rounds(77, 0, 6)
    servers = server_list(config)
    own = verify.run_own(config, servers, rounds, "bfloat16")
    nums = verify.replay(config, servers, rounds, own)
    assert any(nums[k] > limits[k] for k in verify.NUMBERS), nums


def test_program_matches_reference_round_by_round():
    """The replay itself: the program's decisions on a few Table III rounds
    lie within float32 rounding of the reference."""
    config = json.loads((run.BENCH / "configs" / "paper-4srv.json").read_text())
    mix = Mix.load(run.BENCH / "traffic" / "table3.json")
    sut = System(config, mix.segments)
    rounds = mix.rounds(5, 0, 4)
    decs = [decisions(sut.run(System.arrivals(r)), r) for r in rounds]
    nums = verify.replay(config, server_list(config), rounds, decs)
    assert nums["decision_gap"] < 1e-5 and nums["time_gap"] < 1e-5
