"""The reference's cached scores against the direct formula they replace.

``DirectScores`` computes every score whole from the segment's state, the
way the reference did before it kept per-server tables: the oracle. The
cached ``reference.Scores`` must agree with it bit for bit, in its tables
after any sequence of places and finishes, and in every result and number
of a run or a replay, in float64 and in the bfloat16 control.
"""
import copy
import json

import numpy as np
import pytest

from bench import grid, reference, run, verify
from bench.generator import Mix
from bench.reference import Reference
from bench.system import server_list


class DirectScores:
    """Each call recomputes the [m, Q, T] degradations of every server."""

    def __init__(self, ref, comp_of, diagD, counts, comp, col0, maxd_now):
        self.ref = ref
        self.comp_of, self.diagD = comp_of, diagD
        self.counts, self.comp, self.col0, self.maxd_now = counts, comp, col0, maxd_now
        self.touched = []  # nothing is kept, so nothing goes stale

    def maxd_after(self, types):
        dpred = np.clip(self.col0[:, None, :] + self.ref.D[:, types, :]
                        - self.diagD[:, None, :], 0.0, 1.0)  # [m, Q, T]
        present = np.repeat((self.counts > 0)[:, None, :], len(types), axis=1)
        present[:, np.arange(len(types)), types] = True
        return np.where(present, dpred, -np.inf).max(axis=2)  # [m, Q]

    def __call__(self, types):
        ref, comp, comp_of = self.ref, self.comp, self.comp_of
        cache_after = (comp[:, None] + comp_of[:, types]) / ref.budget[:, None]
        maxd_after = self.maxd_after(types)
        slack = np.minimum(ref.limit - maxd_after, 1.0 - cache_after)
        slack = np.where(ref.active[:, None], slack, -np.inf)
        feas = ((maxd_after < ref.limit) & (cache_after <= 1.0)
                & ref.active[:, None])
        sc = ref.q(0.5 * (comp_of[:, types] / ref.budget[:, None] + maxd_after
                          - self.maxd_now[:, None]))
        return np.where(feas, sc, np.inf).T, slack.T, feas.T


class CheckedScores(reference.Scores):
    """The cached scores, each call checked against the oracle on the same
    state; every ``full_every``-th call compares the whole [m, T] table."""

    full_every = 400
    calls = 0

    def __call__(self, types):
        got = super().__call__(types)
        direct = DirectScores(self.ref, self.comp_of, self.diagD, self.counts,
                              self.comp, self.col0, self.maxd_now)
        for a, b in zip(got, direct(types)):
            assert a.dtype == b.dtype and np.array_equal(a, b)
        CheckedScores.calls += 1
        if CheckedScores.calls % self.full_every == 0:
            every = np.arange(grid.T)
            assert np.array_equal(self.maxd_after(every), direct.maxd_after(every))
        return got


def paper_config():
    return json.loads((run.BENCH / "configs" / "paper-4srv.json").read_text())


def loaded_config(m=600):
    """600 servers alternating M1 and M2 (Table I), the paper cell's
    estimator and fleet settings, a uniform prior of 0.2."""
    config = copy.deepcopy(paper_config())
    config["servers"] = [{"name": f"s{i}", "class": ("M1", "M2")[i % 2]} for i in range(m)]
    config["prior"] = 0.2
    return config


LOADED_MIX = {"segments_per_round": 1, "per_segment": 2400, "arrival_rate_per_s": 1e6,
              "mix": [["64KB", "64MB", 0.85], ["64KB", "8MB", 0.07],
                      ["4KB", "32KB", 0.05], ["16KB", "1MB", 0.03]]}


def case(name):
    """(config, rounds) of a case: six table3 rounds, or one round of the
    loaded fleet (2,400 arrivals in one burst, seed 7)."""
    if name == "table3":
        return paper_config(), Mix.load(run.BENCH / "traffic" / "table3.json").rounds(77, 0, 6)
    return loaded_config(), Mix(LOADED_MIX).rounds(7, 0, 1)


@pytest.fixture
def checked(monkeypatch):
    CheckedScores.calls = 0
    monkeypatch.setattr(reference, "Scores", CheckedScores)


def test_tables_match_direct_after_random_places_and_finishes(checked):
    """600 servers, a seeded random D and fleet: 1,500 tasks of random types
    placed on random servers (every arrival judged); then 400 tasks of 12
    types placed by the reference itself on 20 servers, with waits and
    drains."""
    rng = np.random.default_rng(20261018)
    config = loaded_config()
    ref = Reference(config, server_list(config))
    ref.D = rng.uniform(0.0, 0.6, ref.D.shape)
    ref.active[rng.choice(ref.m, 40, replace=False)] = False

    def tasks(n, types):
        wtype = rng.choice(types, n)
        return (wtype, np.minimum(grid.TYPE_FS[wtype], 256 * grid.MB),
                np.sort(rng.uniform(0.0, 0.05, n)))

    n = 1500
    wtype, nbytes, arr = tasks(n, np.arange(grid.T))
    forced = dict(placement=rng.choice(np.flatnonzero(ref.active), n),
                  was_queued=np.zeros(n, bool), place_time=arr,
                  finish_time=np.full(n, np.inf))
    res = ref.run_segment(wtype, nbytes, arr, forced=forced)
    assert np.isfinite(res.finish_time).all()
    assert CheckedScores.calls == n

    ref.active[:] = False
    ref.active[rng.choice(ref.m, 20, replace=False)] = True
    wtype, nbytes, arr = tasks(400, rng.choice(grid.T, 12, replace=False))
    own = ref.run_segment(wtype, nbytes, arr)
    assert own.was_queued.sum() > 20 and (own.placement >= 0).sum() > 300
    assert CheckedScores.calls > n + 400


@pytest.fixture
def segments(monkeypatch):
    """Every SegmentResult the reference makes, in order."""
    out = []
    orig = Reference.run_segment

    def run_segment(self, *a, **kw):
        res = orig(self, *a, **kw)
        out.append(res)
        return res

    monkeypatch.setattr(Reference, "run_segment", run_segment)
    return out


def _run(config, rounds, dtype, segments):
    """The reference's own run in ``dtype``, then the float64 replay of its
    decisions: (decisions, numbers, every SegmentResult of the two)."""
    del segments[:]
    servers = server_list(config)
    own = verify.run_own(config, servers, rounds, dtype)
    nums = verify.replay(config, servers, rounds, own)
    return own, nums, list(segments)


FIELDS = ("wtype", "nbytes", "placement", "was_queued", "place_time", "finish_time")


@pytest.mark.parametrize("name,dtype", [("table3", "float64"), ("table3", "bfloat16"),
                                        ("loaded", "float64"), ("loaded", "bfloat16")])
def test_run_and_replay_identical_with_direct_scores(name, dtype, segments, monkeypatch):
    config, rounds = case(name)
    own_c, nums_c, segs_c = _run(config, rounds, dtype, segments)
    monkeypatch.setattr(reference, "Scores", DirectScores)
    own_d, nums_d, segs_d = _run(config, rounds, dtype, segments)
    assert nums_c == nums_d
    assert len(segs_c) == len(segs_d) == 2 * sum(len(r.segments) for r in rounds)
    for a, b in zip(segs_c, segs_d):
        for f in FIELDS:
            assert np.array_equal(getattr(a, f), getattr(b, f)), f
        assert a.events == b.events
        assert (a.decision_gap, a.time_gap, a.health_gap) == \
            (b.decision_gap, b.time_gap, b.health_gap)
        for x, y in zip(a._obs, b._obs):
            assert np.array_equal(x, y)
    for ra, rb in zip(own_c, own_d):
        for sa, sb in zip(ra, rb):
            assert sa["events"] == sb["events"]
    if name == "loaded":
        # the queue binds: a sizeable share of the burst waits
        queued = np.mean([s.was_queued.mean() for s in segs_c[: len(rounds[0].segments)]])
        assert queued > 0.1
