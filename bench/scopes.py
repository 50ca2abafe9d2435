"""The traced run split by named layer: the program's host sub-spans and the
device program's ``obs.*`` scopes.

Host: the mean per round of each ``closed_loop.pack.*`` and
``closed_loop.dispatch.*`` sub-span, from the program's span log.

Device: on the first device plane, each instant of busy time over the
traced rounds goes to the innermost operation covering it (the rule
``tracing.attribute`` uses for gaps), and from there to the ``obs.*`` scopes
in that operation's name path (its ``op_name`` metadata; an op the
compiler made, with none, takes the path of the op enclosing it). The
trace's op events name the HLO instruction only; its metadata plane holds
each program's ``HloProto``, from which the paths are read. Time under none of
the four scopes of the scan step (the segment's event loop, the estimator,
the detector, the D re-blend) is ``loop_other_ms``, so the four and the
remainder sum to the plane's busy time. Each is reported in ms per round.

A metric reader gets only the run. So this module finds the run's profiler
slice where ``bench/run.py`` put it (``<out>/<workload>/trace``, from the
run's command line), reduces it once per run, and writes what it found
beside it: ``layers_seed<n>.json`` (the split, the operations that make up
each part, the idle gaps by innermost host span and sub-span) and
``spans_seed<n>.json`` (every span of the window, stamped on the profiler's
host clock, with its round). A program without these spans or scopes
yields no value for them, and nothing raises.
"""
from __future__ import annotations

import argparse
import bisect
import dataclasses
import glob
import heapq
import json
import sys
import time
import traceback
from collections import defaultdict
from pathlib import Path
from typing import NamedTuple

from bench import tracing

ROOT = Path(__file__).resolve().parent.parent

#: per-layer metric -> the program span it reads (mean ms per round)
SPAN_METRICS = {
    "dispatch_call_ms": "closed_loop.dispatch.call",
    "dispatch_wait_ms": "closed_loop.dispatch.wait",
    "dispatch_fetch_ms": "closed_loop.dispatch.fetch",
    "pack_arrivals_ms": "closed_loop.pack.arrivals",
    "pack_tables_ms": "closed_loop.pack.tables",
    "pack_state_ms": "closed_loop.pack.state",
}
#: per-layer metric -> a top-level scope of the scan step: all device time
#: under it, inner scopes included
STEP_SCOPES = {
    "loop_events_ms": "obs.segment_event_loop",
    "loop_estimate_ms": "obs.estimate",
    "loop_detect_ms": "obs.detect",
    "loop_d_refresh_ms": "obs.d_refresh",
}
#: per-layer metric -> a scope inside the event loop: device time whose
#: innermost scope it is (so the arms exclude the scorer they call)
LOOP_SCOPES = {
    "loop_rates_ms": "obs.rates",
    "loop_arrive_ms": "obs.arrive",
    "loop_drain_ms": "obs.drain",
    "loop_finish_ms": "obs.finish",
    "loop_score_ms": "obs.score",
}
OTHER = "loop_other_ms"


class Op(NamedTuple):
    start: float  # seconds on the trace's clock
    end: float
    path: str  # the op's name path ("" where the program gives none)


@dataclasses.dataclass
class Slice:
    ops: list[Op]  # operations of the first device plane
    host: list[tracing.Event]  # rounds and every closed_loop.* span
    origin_ns: int | None  # the trace's zero on time.time_ns()'s clock
    #: the obs.* scopes named anywhere in the programs these ops belong to
    scopes: frozenset[str] = frozenset()


def _varint(buf, i: int) -> tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf):
    """(field number, value) of each field of a serialized protobuf message;
    a length-delimited value is a memoryview into ``buf``."""
    buf = memoryview(buf)
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            v, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            v, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"protobuf wire type {wire} is not read here")
        yield key >> 3, v


def _text(v) -> str:
    return bytes(v).decode("utf-8", "replace")


def hlo_op_names(hlo_proto: bytes) -> dict[str, str]:
    """Instruction name -> ``op_name`` metadata, from a serialized ``HloProto``
    (hlo_module 1 -> computations 3 -> instructions 2 -> name 1, metadata 7
    -> op_name 2)."""
    out: dict[str, str] = {}
    for f, module in _fields(hlo_proto):
        if f != 1:
            continue
        for f, comp in _fields(module):
            if f != 3:
                continue
            for f, inst in _fields(comp):
                if f != 2:
                    continue
                name = path = ""
                for g, v in _fields(inst):
                    if g == 1:
                        name = _text(v)
                    elif g == 7:
                        path = next((_text(w) for h, w in _fields(v) if h == 2), "")
                if name:
                    out[name] = path
    return out


def program_op_names(xspace: bytes) -> dict[str, dict[str, str]]:
    """Per program, as the trace names it (``jit_f(<program id>)``): its
    instructions' name paths, from the ``Hlo Proto`` stats of the trace's
    ``/host:metadata`` plane (XSpace planes 1; XPlane name 2, event_metadata
    4, stat_metadata 5; XEventMetadata name 2, stats 5; XStat metadata_id 1,
    bytes_value 6)."""
    for f, plane in _fields(xspace):
        if f != 1:
            continue
        fields = list(_fields(plane))
        if next((_text(v) for g, v in fields if g == 2), "") != "/host:metadata":
            continue
        stat_names = {}
        for g, entry in fields:
            if g == 5:
                kv = dict(_fields(entry))
                stat_names[kv.get(1)] = next(
                    (_text(v) for h, v in _fields(kv.get(2, b"")) if h == 2), "")
        out = {}
        for g, entry in fields:
            if g != 4:
                continue
            md = dict(_fields(entry)).get(2, b"")
            name, protos = "", []
            for h, v in _fields(md):
                if h == 2:
                    name = _text(v)
                elif h == 5:
                    st = dict(_fields(v))
                    if stat_names.get(st.get(1)) == "Hlo Proto" and 6 in st:
                        protos.append(st[6])
            for proto in protos:
                out[name] = hlo_op_names(proto)
        return out
    return {}


def _instruction(name: str) -> str:
    """The instruction an op event names: its ``hlo_op`` or the name before
    `` = `` in the HLO text a TPU trace gives."""
    return name[1:].split(" = ", 1)[0] if name.startswith("%") else name


def innermost_scope(path: str) -> str | None:
    scopes = [p for p in path.split("/") if p.startswith("obs.")]
    return scopes[-1] if scopes else None


def inherit_paths(ops: list[Op]) -> list[Op]:
    """Ops the compiler made (copies, loop-carry plumbing) carry no name
    path: each takes the path of the innermost op enclosing it that has one."""
    out, stack = [], []  # stack: (end, path) of enclosing named ops
    for o in sorted(ops, key=lambda o: (o.start, -o.end)):
        while stack and stack[-1][0] <= o.start:
            stack.pop()
        if not o.path and stack:
            o = o._replace(path=stack[-1][1])
        if o.path:
            stack.append((o.end, o.path))
        out.append(o)
    return out


def self_times(ops: list[Op], lo: float, hi: float) -> dict[str, float]:
    """Seconds of [lo, hi] in which each path's operation is the innermost
    (shortest) one running; their sum is the busy union over [lo, hi]."""
    ops = sorted((o for o in ops if o.end > lo and o.start < hi), key=lambda o: o.start)
    points = sorted({max(lo, min(hi, t)) for o in ops for t in (o.start, o.end)})
    out: dict[str, float] = defaultdict(float)
    active: list[tuple[float, int, Op]] = []  # (duration, order, op)
    i = 0
    for a, b in zip(points, points[1:]):
        while i < len(ops) and ops[i].start <= a:
            heapq.heappush(active, (ops[i].end - ops[i].start, i, ops[i]))
            i += 1
        # an op that has ended leaves the heap once it reaches the top
        while active and active[0][2].end <= a:
            heapq.heappop(active)
        if active:
            out[active[0][2].path] += b - a
    return dict(out)


def split(times: dict[str, float], present) -> dict[str, float]:
    """Seconds per metric of the device split (``STEP_SCOPES``, ``LOOP_SCOPES``,
    ``OTHER``) from self times by name path; a scope not ``present`` in the
    program gives no metric."""
    out = {k: 0.0 for k, v in (*STEP_SCOPES.items(), *LOOP_SCOPES.items())
           if v in present}
    out[OTHER] = 0.0
    step_of = {v: k for k, v in STEP_SCOPES.items()}
    loop_of = {v: k for k, v in LOOP_SCOPES.items()}
    for path, s in times.items():
        parts = path.split("/")
        out[next((step_of[p] for p in parts if p in step_of), OTHER)] += s
        inner = loop_of.get(innermost_scope(path))
        if inner is not None:
            out[inner] += s
    return out


def load(path: "str | Path", device_lines=tracing.device_ops_lines) -> Slice:
    """Operations with their name paths on the first device plane, and the
    host's round and ``closed_loop.*`` annotations, from an ``.xplane.pb``.

    An op belongs to the program running on its plane's ``XLA Modules`` line
    when it starts (a TPU trace), else to the one its ``hlo_module`` and
    ``program_id`` stats name (XLA's CPU client)."""
    import jax

    path = Path(path)
    names = program_op_names(path.read_bytes())
    pd = jax.profiler.ProfileData.from_file(str(path))
    ops: dict[str, list[Op]] = {}
    host: list[tracing.Event] = []
    origin = None
    progs: set[str] = set()
    for plane in pd.planes:
        lines = list(plane.lines)
        for k, v in plane.stats:
            if k == "profile_start_time":
                origin = int(v)
        keep = set(device_lines(plane.name, [ln.name for ln in lines]))
        if keep:
            runs = sorted((e.start_ns, e.start_ns + e.duration_ns, e.name)
                          for ln in lines if ln.name == "XLA Modules" for e in ln.events)
            starts = [r[0] for r in runs]
            plane_ops = []
            for ln in lines:
                if ln.name not in keep:
                    continue
                for e in ln.events:
                    if runs:
                        j = bisect.bisect_right(starts, e.start_ns) - 1
                        prog = runs[j][2] if j >= 0 and e.start_ns < runs[j][1] else ""
                        inst = _instruction(e.name)
                    else:
                        st = dict(e.stats)
                        prog = f"{st.get('hlo_module')}({st.get('program_id')})"
                        inst = st.get("hlo_op", "")
                    progs.add(prog)
                    plane_ops.append(Op(
                        e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9,
                        names.get(prog, {}).get(inst, "")))
            ops[plane.name] = plane_ops
        if plane.name.startswith("/host:"):
            host.extend(tracing.Event(e.name, e.start_ns * 1e-9,
                                      (e.start_ns + e.duration_ns) * 1e-9)
                        for ln in lines for e in ln.events
                        if e.name == tracing.ROUND_SPAN
                        or e.name.startswith("closed_loop."))
    first = sorted(ops)[0] if ops else None
    scopes = {p for prog in progs for path in names.get(prog, {}).values()
              for p in path.split("/") if p.startswith("obs.")}
    return Slice(ops[first] if first else [], host, origin, frozenset(scopes))


def reduce_slice(sl: Slice, top: int = 12) -> dict | None:
    """The device split per round (ms), the operations behind each part, and
    the idle gaps by innermost host span; None where the slice holds no
    round, no operation or no name path."""
    rounds = [ev for ev in sl.host if ev.name == tracing.ROUND_SPAN]
    if not rounds or not sl.ops or not any(o.path for o in sl.ops):
        return None
    lo, hi = min(ev.start for ev in rounds), max(ev.end for ev in rounds)
    times = self_times(inherit_paths(sl.ops), lo, hi)
    parts = split(times, sl.scopes)
    n = len(rounds)
    by_part: dict[str, list] = defaultdict(list)
    step_of = {v: k for k, v in STEP_SCOPES.items()}
    for path, s in sorted(times.items(), key=lambda x: -x[1]):
        part = next((step_of[p] for p in path.split("/") if p in step_of), OTHER)
        if len(by_part[part]) < top:
            by_part[part].append([path, 1e3 * s / n])
    busy = tracing.union([(o.start, o.end) for o in sl.ops])
    gaps = sorted(((tracing.attribute(g, sl.host), g[1] - g[0])
                   for g in tracing.gaps(busy, lo, hi)), key=lambda x: -x[1])
    return {"rounds": n, "window_s": hi - lo,
            "busy_ms": 1e3 * sum(times.values()) / n,
            "metrics": {k: 1e3 * v / n for k, v in parts.items()},
            "top_ops_ms": dict(by_part), "idle_gaps": [list(g) for g in gaps[:top]]}


def span_rows(spans) -> list[dict]:
    """Every span of the window in open order, with its round (the count of
    top-level ``closed_loop.pack`` spans opened before it, from 0). A span
    of a program that stamps no ``start_ns`` gives its ``time.time()``
    start instead."""
    rows, r = [], -1
    for s in sorted(spans, key=lambda s: s.id):
        if s.name == "closed_loop.pack" and s.depth == 0:
            r += 1
        start = getattr(s, "start_ns", None)
        rows.append({"name": s.name,
                     "start_ns": round(s.t_start * 1e9) if start is None else start,
                     "duration_s": s.duration_s, "round": r, "depth": s.depth})
    return rows


def _run_args(argv=None) -> argparse.Namespace:
    """The out directory, workload and seed of the running ``bench/run.py``."""
    ap = argparse.ArgumentParser(add_help=False, allow_abbrev=False)
    ap.add_argument("--workload")
    ap.add_argument("--seed")
    ap.add_argument("--out", default=str(ROOT / ".bench_out"))
    return ap.parse_known_args(sys.argv[1:] if argv is None else argv)[0]


def of(run) -> dict:
    """Every metric of this module that the run has, reduced once per run."""
    cached = getattr(run, "layers", None)
    if cached is None:
        cached = run.layers = _measure(run)
    return cached


def _measure(run) -> dict:
    out: dict = {}
    spans = getattr(run, "spans", None)
    if spans is not None:
        for metric, name in SPAN_METRICS.items():
            d = [s.duration_s for s in spans.spans if s.name == name]
            if d:
                out[metric] = 1e3 * sum(d) / len(d)
    args = _run_args()
    if args.workload is None:
        return out
    out_dir = Path(args.out) / args.workload
    record: dict = {}
    found = sorted(glob.glob(str(out_dir / "trace/plugins/profile/*/*.xplane.pb")))
    if getattr(run, "reduction", None) is not None and found:
        t0 = time.perf_counter()
        try:
            sl = load(found[-1])
            red = reduce_slice(sl)
        except (OSError, ValueError, KeyError, IndexError):
            # a trace this reader cannot parse gives no device metric; the
            # run and its other metrics go on
            traceback.print_exc()
        else:
            record = {"origin_ns": sl.origin_ns, "device": red,
                      "reduce_s": time.perf_counter() - t0}
            if red is not None:
                out.update(red["metrics"])
    if spans is not None and out_dir.is_dir():
        (out_dir / f"spans_seed{args.seed}.json").write_text(
            json.dumps(span_rows(spans.spans)))
        (out_dir / f"layers_seed{args.seed}.json").write_text(
            json.dumps(dict(record, metrics=out), indent=1))
    return out
