"""The system under test: the served closed-loop path of the ``repro`` package.

``AdaptiveEngine.run(arrivals, segments, device_loop=True)`` is one round:
``_pack_device_loop`` -> ``run_closed_loop`` -> ``_trace_segment`` -> the
host epilogue. This module builds the engine from a configuration file,
turns generated rounds into the engine's arrival lists, and reads its
decisions back in the reference's terms. Nothing else of the benchmark
imports the program.
"""
from __future__ import annotations

import numpy as np

from .generator import SEGMENT_SPACING_S, Round


def server_list(config: dict) -> list[tuple[str, str]]:
    """(name, class) per server, in fleet order."""
    return [(s["name"], s["class"]) for s in config["servers"]]


#: (rs, fs, bytes) -> its Workload
_WORKLOADS: dict = {}


class System:
    """One long-lived scheduler over the configuration's fleet."""

    def __init__(self, config: dict, segments: int):
        from repro.core import AdaptiveEngine, ServerSpec
        from repro.fleet import FleetController

        fl, est = config["fleet"], config["estimator"]
        if fl["pools"] != "per_server":
            raise ValueError("the benchmark runs per-server estimator rows only")
        specs = [ServerSpec(name=name, **config["classes"][c])
                 for name, c in server_list(config)]
        if len(set(specs)) != len(specs):
            raise ValueError("servers must be distinct machines (one pool each)")
        self.segments = segments
        self.engine = AdaptiveEngine(
            specs, prior=config["prior"], alpha=float(config["alpha"]),
            objective=config["objective"], lr=est["lr"], decay=est["decay"],
            confidence_floor=est["confidence_floor"],
            max_lost_frac=est["max_lost_frac"],
            fleet=FleetController(
                pools="spec", cusum_k=fl["cusum_k"], cusum_h=fl["cusum_h"],
                level_decay=fl["level_decay"], fail_floor=fl["fail_floor"],
                min_exposure=fl["min_exposure"],
                max_lost_frac=fl["max_lost_frac"],
                warmup_segments=fl["warmup_segments"]))
        first = self.engine.estimators[0]
        if (first.step_damp, first.solo_eps) != (est["step_damp"], est["solo_eps"]):
            raise ValueError("estimator step_damp/solo_eps differ from the config")

    @staticmethod
    def arrivals(rnd: Round) -> list:
        """The engine's (time, Workload) list for a round, segments in order.
        A workload is immutable, so rounds share one object per kind of task."""
        from repro.core import Workload

        out = []
        for k, seg in enumerate(rnd.segments):
            base = k * SEGMENT_SPACING_S
            for t, rs, fs, b in zip(seg.time.tolist(), seg.rs.tolist(), seg.fs.tolist(),
                                    seg.nbytes.tolist()):
                w = _WORKLOADS.get((rs, fs, b))
                if w is None:
                    w = _WORKLOADS[rs, fs, b] = Workload(fs=fs, rs=rs, data_total=b)
                out.append((base + t, w))
        return out

    def run(self, arrivals: list):
        return self.engine.run(arrivals, segments=self.segments, device_loop=True)


def decisions(result, rnd: Round) -> list[dict]:
    """Per segment: what the program decided, on the segment's own clock.

    ``placement`` is -1 for a task never placed; times of a task never placed
    or never finished are -1 and inf, as the engine reports them.
    """
    out = []
    for k, (seg_res, events) in enumerate(zip(result.segments, result.health)):
        t0 = k * SEGMENT_SPACING_S + float(rnd.segments[k].time[0])
        pt = np.asarray(seg_res.place_times, np.float64)
        ft = np.asarray(seg_res.finish_times, np.float64)
        out.append(dict(
            placement=np.array([-1 if p is None else p for p in seg_res.placements],
                               np.int64),
            was_queued=np.asarray(seg_res.was_queued, bool),
            place_time=np.where(pt >= 0.0, pt - t0, pt),
            finish_time=np.where(np.isfinite(ft), ft - t0, ft),
            events=[(ev.kind, int(ev.server)) for ev in events]))
    return out
