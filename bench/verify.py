"""The comparison that decides ``correct``: the program against the reference.

The reference replays every round the program ran, warm-up rounds included
(they moved the scheduler's state), following the program's decisions, and
reads two numbers, each the widest over all rounds:

  decision_gap  how far a decision lies from what the reference allows: the
                score by which the chosen server trails the best feasible
                one, how far past a criterion it lies, or, for a task queued
                or left waiting, how far inside both criteria the best
                server was; and for a server on whose eviction program and
                reference disagree, its failure statistic's distance from
                the threshold (a health action is a decision too)
  time_gap      the widest difference of a place or finish time from the
                reference's, over the segment's makespan

``correct`` holds when each is at or under its limit (``bench/limits``).
"""
from __future__ import annotations

import numpy as np

from .reference import BIG, Reference

NUMBERS = ("decision_gap", "time_gap")
#: rounds whose every decision is judged (the rest are replayed untimed)
JUDGED_ROUNDS = 64


def _time_gap(ref, prog) -> float:
    fin = ref.finish_time[np.isfinite(ref.finish_time)]
    scale = float(fin.max()) if fin.size and fin.max() > 0 else 1.0
    g = 0.0
    for a, b in ((ref.place_time, prog["place_time"]), (ref.finish_time, prog["finish_time"])):
        fa, fb = np.isfinite(a) & (a >= 0), np.isfinite(b) & (b >= 0)
        if (fa != fb).any():
            return BIG
        if fa.any():
            g = max(g, float(np.abs(a[fa] - b[fa]).max()) / scale)
    return g


def _tasks(seg, requeue):
    wt, nb, at = seg.wtype, seg.nbytes, seg.time
    if requeue is not None and requeue[0].size:
        rt, rb = requeue
        wt = np.concatenate([rt, wt])
        nb = np.concatenate([rb, nb])
        at = np.concatenate([np.zeros(rt.size), at])
    return wt, nb, at


def _requeue(wt, nb, placement, events):
    evicted = [sv for kind, sv in events if kind == "evict"]
    if not evicted:
        return None
    sel = np.isin(placement, evicted) | (placement < 0)
    return wt[sel], nb[sel]


def judged_rounds(n: int, seed: int, budget: int = JUDGED_ROUNDS) -> set[int]:
    """Which of ``n`` rounds have every decision judged: all where ``n`` is
    within the budget, else the first, the last, and a sample drawn from the
    seed. The others are replayed for their times and observations only."""
    if n <= budget:
        return set(range(n))
    rng = np.random.default_rng([abs(int(seed)) & 0xFFFFFFFF, n])
    return {0, n - 1} | set(rng.choice(np.arange(1, n - 1), budget - 2, replace=False).tolist())


def replay(config, servers, rounds, decisions, judged: "set[int] | None" = None
           ) -> dict[str, float]:
    """The numbers for the program's ``decisions`` of ``rounds``."""
    ref = Reference(config, servers)
    worst = dict.fromkeys(NUMBERS, 0.0)
    for r, (rnd, prog_segs) in enumerate(zip(rounds, decisions)):
        requeue = None
        for k, seg in enumerate(rnd.segments):
            wt, nb, at = _tasks(seg, requeue)
            prog = prog_segs[k]
            if prog["placement"].size != wt.size:
                return dict.fromkeys(NUMBERS, BIG)
            res = ref.run_segment(wt, nb, at, forced=prog,
                                  judged=judged is None or r in judged)
            ref.observe(res, forced_events=prog["events"])
            worst["decision_gap"] = max(worst["decision_gap"], res.decision_gap,
                                        res.health_gap)
            worst["time_gap"] = max(worst["time_gap"], _time_gap(res, prog))
            requeue = _requeue(wt, nb, prog["placement"], prog["events"])
    return worst


def run_own(config, servers, rounds, dtype: str) -> list[list[dict]]:
    """The reference deciding for itself (the control, in ``dtype``): its
    decisions in the program's format, for ``replay`` to judge."""
    ref = Reference(config, servers, dtype=dtype)
    out = []
    for rnd in rounds:
        requeue, segs = None, []
        for seg in rnd.segments:
            wt, nb, at = _tasks(seg, requeue)
            res = ref.run_segment(wt, nb, at)
            ref.observe(res)
            segs.append(dict(placement=res.placement, was_queued=res.was_queued,
                             place_time=res.place_time, finish_time=res.finish_time,
                             events=res.events))
            requeue = _requeue(wt, nb, res.placement, res.events)
        out.append(segs)
    return out
