"""Plain reference of the closed-loop consolidation scheduler, in float64.

It imports nothing of the program and takes nothing the program made: the
server classes' constants come from the configuration file, the workload
grid from ``bench.grid``, the arrivals from the traffic generator. It is the
paper's method written out straight:

- Physics (the co-run contention model, Sec. IV): per server class, solo and
  cache-lost throughputs per type, the pairwise slowdown d[u, t] that a type-u
  co-runner imposes on type t (per shared resource: excess over capacity plus
  a baseline interference, composed over memory, disk and CPU), and the
  physical LLC tolerance. A task's rate is its base rate times
  prod_u (1 - d[u, t]) ** n_u / (1 - d[t, t]) over its server's residents.
- Scheduler (Fig. 8, Sec. V): each segment starts from an empty cluster. An
  arrival goes to the feasible server (criterion 1: competing cache bytes
  within alpha x LLC; criterion 2: no resident's additive-model degradation
  over the estimated D at 50% or more) whose load rises least, half the
  cache increase plus half the max-degradation increase; else it waits. A
  completion places the first waiting task that fits, again and again.
  Completions within a relative 1e-5 of the earliest resolve lowest server
  and slot first; score ties resolve to the lowest server within 1e-6.
- Estimator: per server, a log-linear model of the observed rate (log base
  rate per type, log(1 - d) per pair) fit by a damped least-squares step per
  segment from each finished task's time-averaged co-residents and log rate;
  the scheduler's D blends each pair towards the prior until its exposure
  reaches the confidence floor.
- Fleet controller: an exposure-weighted residual level per server against
  the fleet median, and the base rate against the nominal prior; a server
  below ``fail_floor`` on either leaves the fleet (never the last one), and
  its in-flight work re-enters at the head of the round's next segment.
  Evidence is discarded during the first ``warmup_segments`` segments.

Two ways to run it. *Replay* follows the program's own decisions (which
server, queue or not, which health actions) and measures, at each one, how
far it lies from what this model allows, while computing every time and
every estimate itself. *Own* makes every decision itself: with ``dtype``
bfloat16 that is the control, the reference in the precision below the
program's float32.
"""
from __future__ import annotations

import dataclasses
import math

import numpy as np

from . import grid

BIG = 1e9  # a gap for an answer that never came or cannot be compared
#: slack within which a replay still follows the program onto a server: a
#: criterion evaluated a hair past its edge in float32 is a tie, not a fault
TIE = 1e-2


def _amortized(bw, ov, rs):
    return rs / (ov + rs / bw)


@dataclasses.dataclass
class ClassTables:
    """One server class's physics, float64, over the 230 grid types."""

    solo: np.ndarray  # [T] bytes/s alone
    lost: np.ndarray  # [T] bytes/s once the LLC is lost
    log_keep: np.ndarray  # [T(u), T(t)] log(1 - d) while the LLC holds
    log_lost: np.ndarray  # [T(u), T(t)] log(1 - d) past the LLC tolerance
    comp: np.ndarray  # [T] competing cache bytes (Eqn 2 terms)
    tol: float  # physical LLC tolerance in bytes
    budget: float  # alpha x LLC: criterion 1's capacity
    profiled: np.ndarray  # [T(u), T(t)] D from pairwise profiling (Sec. IV.B)

    @classmethod
    def build(cls, c: dict, alpha: float) -> "ClassTables":
        rs, fs = grid.TYPE_RS, grid.TYPE_FS
        llc = float(c["llc_bytes"])
        resident = fs <= llc
        l1 = _amortized(c["bw_l1_read"], c["ov_l12"], rs)
        l2 = _amortized(c["bw_l2_read"], c["ov_l12"], rs)
        solo = np.where(resident, l1, l2)  # reads: level 1 in the LLC, else 2
        lost = l2  # a resident read falls to level 2; others already are
        cap = np.array([c["shared_bw"], c["bw_l3_write"], float(c["cores"])])

        def demand(base, level1):
            mem = np.where(level1, 0.05 * base, base)
            disk = np.zeros_like(base)  # reads trickle nothing to disk
            cpu = base / rs * (c["cpu_req_cost"] + rs * c["cpu_byte_cost"])
            dem = np.stack([mem, disk, cpu], axis=1)  # [T, 3]
            sens = np.stack([np.minimum(1.0, mem / base),
                             np.minimum(1.0, disk / base),
                             np.minimum(1.0, cpu)], axis=1)
            return dem, sens

        def log_pair(dem, sens):
            d = _pair_d(dem[:, None, :], dem[None, :, :], sens[None, :, :], cap)
            return np.log1p(-np.clip(d, 0.0, 1.0 - 1e-9))

        dk, sk = demand(solo, resident)
        dl, sl = demand(lost, np.zeros_like(resident))
        comp = rs + np.where(resident, fs, 0.0)
        tol = float(c["llc_tolerance"]) * llc
        # pairwise profiling: each pair co-runs alone, with its own cache outcome
        over = (comp[:, None] + comp[None, :]) > tol  # [u, t]
        ov = over[:, :, None]
        d_pair = _pair_d(np.where(ov, dl[:, None, :], dk[:, None, :]),
                         np.where(ov, dl[None, :, :], dk[None, :, :]),
                         np.where(ov, sl[None, :, :], sk[None, :, :]), cap)
        base_t = np.where(over, lost[None, :], solo[None, :])
        profiled = 1.0 - base_t * (1.0 - d_pair) / solo[None, :]
        return cls(solo=solo, lost=lost, log_keep=log_pair(dk, sk),
                   log_lost=log_pair(dl, sl), comp=comp, tol=tol,
                   budget=alpha * llc, profiled=profiled)


def _pair_d(dem_i, dem_j, sens_j, cap):
    """d[u, t]: per shared resource, the excess of the pair's demand over
    capacity plus the aggressor's baseline interference, composed over the
    resources the target is exposed to (arrays broadcast over [u, t, r])."""
    total = dem_i + dem_j
    with np.errstate(divide="ignore", invalid="ignore"):
        excess = np.where(total > 0, np.maximum(0.0, 1.0 - cap / total), 0.0)
    baseline = dem_i / (dem_i + 20.0 * cap)
    slow = 1.0 - (1.0 - excess) * (1.0 - baseline)
    return 1.0 - np.prod(1.0 - sens_j * slow, axis=-1)


class Scores:
    """Each type's score, slack and feasibility on every server, over one
    segment's changing cluster.

    It reads the segment's per-server state arrays in place (``counts``,
    ``comp``, ``col0``, ``maxd_now``), which only a place or a finish
    changes, one server at a time, and the estimate ``D`` and activity
    fixed for the segment. The costly term, the largest predicted
    degradation on a server once a type-u task joins it (a max over the
    types present there), is kept per (server, type) and recomputed only
    where that server changed since (listed in ``touched``), or where the
    type had not been asked for on it yet. Every element comes from the
    same float64 expression as computed whole, so results do not depend on
    the caching.
    """

    def __init__(self, ref: "Reference", comp_of, diagD, counts, comp, col0, maxd_now):
        self.ref = ref
        self.comp_of, self.diagD = comp_of, diagD
        self.counts, self.comp, self.col0, self.maxd_now = counts, comp, col0, maxd_now
        self.maxd = np.zeros(counts.shape)  # [m, T]
        self.fresh = np.zeros(counts.shape, bool)
        #: servers whose residents changed since the last ask; a list, as
        #: most segments of a replay are never scored
        self.touched: list[int] = []

    def maxd_after(self, types) -> np.ndarray:
        """[m, Q]: per server, the max over the types present there with
        each of ``types`` added of the clipped additive degradation."""
        if self.touched:
            self.fresh[self.touched] = False
            self.touched.clear()
        for u in set(types.tolist()):
            fresh = self.fresh[:, u]  # a view: marking it marks the table
            if not fresh.all():
                ss = np.flatnonzero(~fresh)
                dpred = np.clip(self.col0[ss] + self.ref.D[ss, u] - self.diagD[ss],
                                0.0, 1.0)  # [P, T]
                present = self.counts[ss] > 0
                present[:, u] = True
                self.maxd[ss, u] = np.where(present, dpred, -np.inf).max(axis=1)
                fresh[ss] = True
        return self.maxd[:, types]

    def __call__(self, types):
        """Scores, slack and feasibility of each type on each server [Q, m]."""
        ref = self.ref
        maxd_after = self.maxd_after(types)
        comp_t = self.comp_of[:, types]
        budget = ref.budget[:, None]
        active = ref.active[:, None]
        cache_after = (self.comp[:, None] + comp_t) / budget
        slack = np.where(active, np.minimum(ref.limit - maxd_after, 1.0 - cache_after),
                         -np.inf)
        feas = (maxd_after < ref.limit) & (cache_after <= 1.0) & active
        sc = ref.q(0.5 * (comp_t / budget + maxd_after - self.maxd_now[:, None]))
        return np.where(feas, sc, np.inf).T, slack.T, feas.T


@dataclasses.dataclass
class SegmentResult:
    """Per task of a segment (requeued work first): decisions and times."""

    wtype: np.ndarray
    nbytes: np.ndarray
    placement: np.ndarray  # server, or -1 never placed
    was_queued: np.ndarray
    place_time: np.ndarray  # -1 never placed
    finish_time: np.ndarray  # inf never finished
    events: list  # [(kind, server)] health actions after the segment
    # replay only: the widest gaps found in this segment
    decision_gap: float = 0.0
    time_gap: float = 0.0
    health_gap: float = 0.0


class Reference:
    """The whole fleet's scheduler state, carried from round to round."""

    def __init__(self, config: dict, servers: list[tuple[str, str]],
                 dtype: str = "float64"):
        self.q = _rounder(dtype)
        alpha = float(config["alpha"])
        names = sorted({c for _, c in servers})
        self.tables = [ClassTables.build(config["classes"][c], alpha) for c in names]
        self.cls = np.array([names.index(c) for _, c in servers])
        self.m = m = len(servers)
        self.limit = float(config["degradation_limit"])
        est, fl = config["estimator"], config["fleet"]
        self.est, self.fl = est, fl
        Tn = grid.T
        if config["prior"] == "profiled":
            priors = [tb.profiled for tb in self.tables]
        else:
            priors = [np.full((Tn, Tn), float(config["prior"]))] * len(self.tables)
        # per class, target-major [t, u] like the estimate it anchors
        self.L_prior_c = np.stack([np.log1p(-np.clip(p, 0.0, 1.0 - 1e-9)).T
                                   for p in priors])
        self.logb_prior = np.log(np.stack([self.tables[c].solo for c in self.cls]))
        # [s, t, u], C-contiguous: the estimator's dot products over a row
        # sum in an order that depends on its strides
        self.L = self.L_prior_c[self.cls].copy()
        self.log_b = self.logb_prior.copy()
        self.n_pair = np.zeros((m, Tn, Tn))
        self.n_base = np.zeros((m, Tn))
        self.D = np.zeros((m, Tn, Tn))  # [s, u, t] the scheduler's estimate
        # every server of a class starts from the same estimate: blend one
        for c in range(len(names)):
            of_c = np.flatnonzero(self.cls == c)
            self._blend(of_c[0], np.arange(Tn))
            self.D[of_c] = self.D[of_c[0]]
        self.level = np.zeros(m)
        self.exposure = np.zeros(m)
        self.active = np.ones(m, bool)
        self.row_live = np.ones(m, bool)
        self.seen = 0
        # per-server constants gathered once
        self.budget = np.array([self.tables[c].budget for c in self.cls])
        self.tol = np.array([self.tables[c].tol for c in self.cls])
        self.solo_c = np.stack([tb.solo for tb in self.tables])  # [C, T]
        self.lost_c = np.stack([tb.lost for tb in self.tables])
        self.ldk_c = np.stack([np.diagonal(tb.log_keep) for tb in self.tables])
        self.ldl_c = np.stack([np.diagonal(tb.log_lost) for tb in self.tables])
        self.log_solo_c = np.log(self.solo_c)
        self.log_lost_c = np.log(self.lost_c)

    # -- the estimate the scheduler reads ---------------------------------
    def _blend(self, s: int, types) -> None:
        w = np.minimum(self.n_pair[s][types] / self.est["confidence_floor"], 1.0)
        Leff = w * self.L[s][types] + (1.0 - w) * self.L_prior_c[self.cls[s]][types]
        self.D[s][:, types] = self.q(np.clip(-np.expm1(Leff), 0.0, 0.999999)).T

    # -- one segment ------------------------------------------------------
    def run_segment(self, wtype, nbytes, arr_time, forced: dict | None = None,
                    judged: bool = True) -> SegmentResult:
        """Run one segment from an empty cluster.

        With ``forced`` (the program's decisions for these tasks) the replay
        follows them and, where ``judged``, records the gaps; without, it
        decides itself. A replay takes the program's servers and its drain
        order (the waiting tasks it placed at each completion, while they
        fit); one that is not judged scores nothing else, yet computes
        every time and every observation the estimator needs.

        Between events on a server its residents, and so their rates, stay
        fixed: each server integrates its resident counts, its time past the
        LLC tolerance and its tasks' log rates lazily, up to the instant
        before anything on it changes.
        """
        q = self.q
        n = wtype.size
        m = self.m
        tabs = self.tables
        cls = self.cls
        comp_of = np.stack([tabs[c].comp for c in cls])  # [m, T]
        diagD = np.einsum("sjj->sj", self.D)  # [m, T]
        counts = np.zeros((m, grid.T))
        comp = np.zeros(m)
        col0 = np.zeros((m, grid.T))
        clog_k = np.zeros((m, grid.T))
        clog_l = np.zeros((m, grid.T))
        maxd_now = np.zeros(m)
        last = np.zeros(m)  # each server's integrals are current up to here
        Ic = np.zeros((m, grid.T))  # time integral of resident counts
        Il = np.zeros(m)  # time spent past the LLC tolerance
        on: list[list[int]] = [[] for _ in range(m)]  # running tasks per server
        slots: list[list[int]] = [[] for _ in range(m)]  # task per slot, -1 free
        srv = np.full(n, -1)
        slot = np.full(n, -1)
        rem = np.zeros(n)
        rate = np.ones(n)
        logr = np.zeros(n)
        tfin = np.full(n, np.inf)  # projected finish of each running task
        running = np.zeros(n, bool)
        queued = np.zeros(n, bool)
        was_q = np.zeros(n, bool)
        place_t = np.full(n, -1.0)
        fin_t = np.full(n, np.inf)
        co_int = np.zeros((n, grid.T))
        lost_int = np.zeros(n)
        logr_int = np.zeros(n)
        Ic0 = np.zeros((n, grid.T))
        Il0 = np.zeros(n)
        gap = 0.0
        now, ai, draining = 0.0, 0, False
        trigger = np.nan  # the program's time of the last completion
        score = Scores(self, comp_of, diagD, counts, comp, col0, maxd_now)
        touched = score.touched

        def judge(t, p):
            """The gap of putting type t on server p (p < 0: queueing it)."""
            sc, slack, feas = (x[0] for x in score(np.array([t])))
            if p < 0:
                return max(0.0, float(slack.max()))
            if not feas[p]:
                return max(float(-slack[p]), 0.0) if np.isfinite(slack[p]) else BIG
            return float(sc[p] - sc.min())

        def choose(t):
            sc, _, feas = (x[0] for x in score(np.array([t])))
            if not feas.any():
                return -1
            return int(np.argmax(sc <= sc.min() + 1e-6))

        def sync(s):
            """Integrate server s's residents up to now."""
            dt = now - last[s]
            if dt > 0.0:
                Ic[s] += dt * counts[s]
                Il[s] += dt * float(comp[s] > self.tol[s])
                ts = on[s]
                if ts:
                    logr_int[ts] += dt * logr[ts]
                    rem[ts] = q(np.maximum(rem[ts] - rate[ts] * dt, 0.0))
            last[s] = now

        def shift(s, t, sign):
            """Add (+1) or remove (-1) one type-t task on server s, then
            re-rate its residents."""
            c = cls[s]
            tb = tabs[c]
            counts[s, t] += sign
            comp[s] += sign * tb.comp[t]
            col0[s] = q(col0[s] + sign * self.D[s, t])
            clog_k[s] += sign * tb.log_keep[t]
            clog_l[s] += sign * tb.log_lost[t]
            pres = counts[s] > 0
            # clip is monotone: the clipped max is the max, clipped
            maxd_now[s] = (min(max(float((col0[s] - diagD[s])[pres].max()), 0.0), 1.0)
                           if pres.any() else 0.0)
            touched.append(s)
            ts = on[s]
            if ts:
                tt = wtype[ts]
                if comp[s] > self.tol[s]:
                    lr = self.log_lost_c[c, tt] + clog_l[s, tt] - self.ldl_c[c, tt]
                else:
                    lr = self.log_solo_c[c, tt] + clog_k[s, tt] - self.ldk_c[c, tt]
                logr[ts] = lr
                rate[ts] = q(np.exp(lr))
                tfin[ts] = now + rem[ts] / rate[ts]

        def place(i, s):
            sync(s)
            k = next((j for j, x in enumerate(slots[s]) if x < 0), len(slots[s]))
            if k == len(slots[s]):
                slots[s].append(i)
            else:
                slots[s][k] = i
            srv[i], slot[i] = s, k
            rem[i] = nbytes[i]
            running[i] = True
            queued[i] = False
            place_t[i] = now
            Ic0[i], Il0[i] = Ic[s], Il[s]
            on[s].append(i)
            shift(s, wtype[i], 1.0)

        def finish(i):
            s = srv[i]
            sync(s)
            dur = now - place_t[i]
            co_int[i] = Ic[s] - Ic0[i]
            co_int[i, wtype[i]] -= dur
            lost_int[i] = Il[s] - Il0[i]
            on[s].remove(i)
            slots[s][slot[i]] = -1
            running[i] = False
            tfin[i] = np.inf
            fin_t[i] = now
            shift(s, wtype[i], -1.0)

        while True:
            idx = np.flatnonzero(running)
            if idx.size:
                tt = np.maximum(tfin[idx] - now, 0.0)
                t_fin_rel = tt.min()
            else:
                t_fin_rel = np.inf
            t_arr = arr_time[ai] if ai < n else np.inf
            drain = draining or (queued.any() and not idx.size and ai >= n)
            if drain:
                qi = np.flatnonzero(queued)
                if forced is None:
                    # the first waiting task that fits anywhere; fit depends
                    # on the type alone, so each distinct type is scored once
                    uniq, inv = np.unique(wtype[qi], return_inverse=True)
                    fits = np.concatenate([np.zeros(0, bool)] + [
                        score(uniq[c:c + 32])[2].any(axis=1)
                        for c in range(0, uniq.size, 32)])[inv]
                    found = int(qi[np.argmax(fits)]) if fits.any() else -1
                    p = choose(wtype[found]) if found >= 0 else -1
                else:
                    # the program's placements at this completion carry its
                    # time (to within the 1e-5 window in which completions
                    # count as simultaneous); the first of them (by index)
                    # that fits on its server is taken (to within TIE: a
                    # flip at a criterion's edge is a tie). Completions at
                    # one instant each drain in turn, so one that does not
                    # fit yet waits for the next of them.
                    pt = forced["place_time"][qi]
                    cand = qi[np.abs(pt - trigger) <= 2e-5 * abs(trigger)]
                    found, p = -1, -1
                    if cand.size:
                        srv_c = forced["placement"][cand]
                        slack_c = score(wtype[cand])[1][np.arange(cand.size), srv_c]
                        ok = np.flatnonzero(slack_c >= -TIE)
                        if ok.size:
                            found, p = int(cand[ok[0]]), int(srv_c[ok[0]])
                    if judged:
                        # a waiting task ahead of the pick (or any, where the
                        # program places none) must not fit
                        ahead = qi[qi < found] if found >= 0 else qi
                        if ahead.size:
                            gap = max(gap, float(score(np.unique(wtype[ahead]))[1].max()))
                        if found >= 0:
                            gap = max(gap, judge(wtype[found], p))
                if found < 0:
                    draining = False
                    if not idx.size and ai >= n:
                        break  # deadlock: the queue fits no empty server
                    continue
                place(found, p)
                draining = True
            elif idx.size and now + t_fin_rel <= t_arr:
                # completions within a relative 1e-5 of the earliest resolve
                # lowest (server, slot) first; the clock moves to that one's
                # time, and any earlier one completes at the same instant
                key = srv[idx] * (n + 1) + slot[idx]
                hit = tt <= t_fin_rel * (1.0 + 1e-5)
                j = int(np.flatnonzero(hit)[np.argmin(key[hit])])
                now = q(now + float(tt[j]))
                finish(int(idx[j]))
                if forced is not None:
                    trigger = forced["finish_time"][idx[j]]
                draining = bool(queued.any())
            elif ai < n:
                now = t_arr
                i = ai
                t = wtype[i]
                if forced is None:
                    p = choose(t)
                else:
                    p = -1 if forced["was_queued"][i] else int(forced["placement"][i])
                    if judged:
                        gap = max(gap, judge(t, p))
                if p >= 0:
                    place(i, p)
                else:
                    queued[i] = was_q[i] = True
                ai += 1
            else:
                break

        out = SegmentResult(wtype, nbytes, srv.copy(), was_q, place_t, fin_t, [],
                            decision_gap=gap)
        out._obs = (co_int, lost_int, logr_int)  # for the estimator update
        return out

    # -- after a segment: estimate, detect, act ---------------------------
    def observe(self, seg: SegmentResult, forced_events=None) -> None:
        """Fold the segment's completions into the estimator and detector,
        then take (or, replaying, judge the program's) health actions."""
        q, est, fl = self.q, self.est, self.fl
        co_int, lost_int, logr_int = seg._obs
        dur = seg.finish_time - seg.place_time
        ok = ((seg.placement >= 0) & (seg.place_time >= 0.0)
              & np.isfinite(seg.finish_time) & (dur > 1e-12))
        idx = np.flatnonzero(ok)
        d = dur[idx]
        s = seg.placement[idx]
        t = seg.wtype[idx]
        y = logr_int[idx] / d
        co = co_int[idx] / d[:, None]
        lost = np.clip(lost_int[idx] / d, 0.0, 1.0)
        live = self.row_live[s]
        use = live & (lost <= est["max_lost_frac"])
        co_sum = co.sum(axis=1)
        co_sq = (co * co).sum(axis=1)
        lr, damp, eps = est["lr"], est["step_damp"], est["solo_eps"]
        if est["decay"] != 1.0:
            raise ValueError("the reference models decay = 1.0 only")

        solo = use & (co_sum <= eps)
        num0, cnt0 = {}, {}
        for j in np.flatnonzero(solo):
            key = (s[j], t[j])
            num0[key] = num0.get(key, 0.0) + (y[j] - self.log_b[key])
            cnt0[key] = cnt0.get(key, 0.0) + 1.0
        for key in num0:
            self.log_b[key] = q(self.log_b[key] + lr * num0[key] / (cnt0[key] + damp))
            self.n_base[key] += cnt0[key]

        cor = use & (co_sum > eps)
        num, den = {}, {}
        for j in np.flatnonzero(cor):
            key = (s[j], t[j])
            pred = self.log_b[key] + co[j] @ self.L[key]
            h = (y[j] - pred) / max(co_sq[j], eps)
            num[key] = num.get(key, 0.0) + h * co[j]
            den[key] = den.get(key, 0.0) + co[j]
        for key in num:
            self.L[key] = q(self.L[key] + lr * num[key] / (den[key] + damp))
            self.n_pair[key] += den[key]
        for sv in {k[0] for k in num}:
            self._blend(sv, np.array(sorted(k[1] for k in num if k[0] == sv)))

        # detector: residuals against the updated model, in task order
        dv = live & (lost <= fl["max_lost_frac"])
        decay = fl["level_decay"]
        for j in np.flatnonzero(dv):
            key = (s[j], t[j])
            r = y[j] - (self.log_b[key] + co[j] @ self.L[key])
            sv = s[j]
            self.level[sv] = q(decay * self.level[sv] + (1.0 - decay) * r)
            self.exposure[sv] = decay * self.exposure[sv] + 1.0
        self.seen += 1
        if self.seen <= fl["warmup_segments"]:
            self.level[:] = 0.0
            self.exposure[:] = 0.0
            mine, stat = [], {}
        else:
            mine, stat = self._evictions()

        if forced_events is None:
            events = [("evict", sv) for sv in mine]
        else:
            events = list(forced_events)
            theirs = {sv for kind, sv in events if kind == "evict"}
            splits = [sv for kind, sv in events if kind != "evict"]
            g = BIG if splits else 0.0
            for sv in set(mine) ^ theirs:
                g = max(g, stat.get(sv, BIG))
            seg.health_gap = g
        for kind, sv in events:
            if kind == "evict":
                self.active[sv] = False
                self.row_live[sv] = False
                self.level[sv] = self.exposure[sv] = 0.0
        seg.events = events

    def _evictions(self) -> tuple[list[int], dict[int, float]]:
        """Servers the failure rules evict now, and for every server the
        distance of its nearest statistic from its threshold."""
        fl = self.fl
        lvl = np.where(self.exposure > 0,
                       self.level / np.maximum((1.0 - fl["level_decay"]) * self.exposure,
                                               1e-12), 0.0)
        seen = self.active & (self.exposure > 0)
        med = float(np.median(lvl[seen])) if seen.any() else 0.0
        floor = math.log(fl["fail_floor"])
        level_hit = (self.exposure >= fl["min_exposure"]) & (lvl - med <= floor)
        tot = self.n_base.sum(axis=1)
        log_ratio = np.where(
            tot >= fl["min_exposure"],
            (self.n_base * (self.log_b - self.logb_prior)).sum(axis=1)
            / np.maximum(tot, 1e-12), 0.0)
        base_hit = log_ratio <= floor
        dist_level = np.where(self.exposure >= fl["min_exposure"],
                              np.abs(lvl - med - floor), BIG)
        dist_base = np.where(tot >= fl["min_exposure"], np.abs(log_ratio - floor), BIG)
        stat = {sv: float(min(dist_level[sv], dist_base[sv])) for sv in range(self.m)}
        active = self.active.copy()
        out = []
        for sv in range(self.m):
            if active[sv] and active.sum() > 1 and (level_hit[sv] or base_hit[sv]):
                active[sv] = False
                out.append(sv)
        return out, stat


def _rounder(dtype: str):
    if dtype == "float64":
        return lambda x: x
    if dtype == "bfloat16":
        import ml_dtypes

        bf = ml_dtypes.bfloat16

        def q(x):
            return np.asarray(x, np.float64).astype(bf).astype(np.float64) \
                if np.ndim(x) else float(np.float64(x).astype(bf))
        return q
    raise ValueError(f"unknown reference dtype {dtype!r}")
