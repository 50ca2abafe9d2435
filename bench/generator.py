"""The one traffic generator: a mix file's parameters -> rounds of arrivals.

A round is what one call of the scheduler decides: ``segments_per_round``
segments of ``per_segment`` arrivals each. Every round of a mix has the same
shape, so no round compiles anew. A mix file (``bench/traffic/<name>.json``)
holds:

  segments_per_round  segments in a round
  per_segment         arrivals per segment (implied by ``prefix`` +
                      ``sequences`` when those fix it)
  arrival_rate_per_s  Poisson rate of the arrivals within a segment
  prefix              tuples "(RS, FS)" that open every segment, in order
  sequences           tuple lists; each segment appends one of them, cycling
                      in an order the seed chooses
  mix                 [[RS, FS, weight], ...]: each segment draws its body
                      from these, in counts proportional to the weights

Work is the same from seed to seed, only its order changes: a segment's
counts per type are the weights' largest-remainder split (ties to the
earlier entry of the file), and its gaps are the ``per_segment`` quantiles
of the exponential distribution, shuffled. The seed and the round
index alone determine a round, so a pool of rounds drawn before the window
holds exactly the rounds any other run of that seed would draw.
"""
from __future__ import annotations

import dataclasses
import json
from pathlib import Path

import numpy as np

from . import grid

#: segments of a round are laid out this far apart in absolute time; the
#: scheduler splits a round by count and times each segment from its start
SEGMENT_SPACING_S = 10.0


@dataclasses.dataclass(frozen=True)
class Segment:
    time: np.ndarray  # f64[n] arrival times from the segment's first arrival
    rs: np.ndarray  # f64[n] request size, snapped to the grid
    fs: np.ndarray  # f64[n] file size, snapped to the grid
    nbytes: np.ndarray  # f64[n] bytes the task moves: one pass over its file
    wtype: np.ndarray  # i32[n] grid type


@dataclasses.dataclass(frozen=True)
class Round:
    index: int
    segments: tuple[Segment, ...]

    @property
    def arrivals(self) -> int:
        return sum(s.time.size for s in self.segments)


class Mix:
    """A parsed mix file; ``round(seed, r)`` draws round ``r`` of ``seed``."""

    def __init__(self, spec: dict):
        self.spec = spec
        self.segments = int(spec["segments_per_round"])
        self.rate = float(spec["arrival_rate_per_s"])
        self.prefix = [t for s in spec.get("prefix", []) for t in grid.parse_tuples(s)]
        self.sequences = [grid.parse_tuples(s) for s in spec.get("sequences", [])]
        self.mix = [(grid.parse_size(rs), grid.parse_size(fs), float(w))
                    for rs, fs, w in spec.get("mix", [])]
        if bool(self.sequences) == bool(self.mix):
            raise ValueError("a mix file gives exactly one of 'sequences' and 'mix'")
        if self.sequences:
            lens = {len(s) for s in self.sequences}
            if len(lens) != 1:
                raise ValueError("every sequence must have the same length")
            n = len(self.prefix) + lens.pop()
            if int(spec.get("per_segment", n)) != n:
                raise ValueError(f"per_segment {spec['per_segment']} != {n}")
            self.per_segment = n
        else:
            self.per_segment = int(spec["per_segment"])
        self.body = self.per_segment - len(self.prefix)
        if self.body <= 0:
            raise ValueError("a segment needs arrivals after its prefix")
        # grid types and bytes of every tuple, looked up once
        self._prefix = _typed(self.prefix)
        self._sequences = [_typed(q) for q in self.sequences]
        self._mix = _typed([x[:2] for x in self.mix])

    @classmethod
    def load(cls, path: "str | Path") -> "Mix":
        return cls(json.loads(Path(path).read_text()))

    @property
    def arrivals_per_round(self) -> int:
        return self.segments * self.per_segment

    def _seq_order(self, seed: int) -> np.ndarray:
        rng = np.random.default_rng(_seed_seq(seed, -1))
        return rng.permutation(len(self.sequences))

    def _body_counts(self) -> np.ndarray:
        w = np.array([x[2] for x in self.mix])
        share = self.body * w / w.sum()
        counts = np.floor(share).astype(int)
        left = self.body - counts.sum()
        if left:
            # the remainder goes to the largest fractions, ties in file order:
            # every seed and every segment holds the same tasks
            counts[np.argsort(-(share - counts), kind="stable")[:left]] += 1
        return counts

    def round(self, seed: int, r: int) -> Round:
        order = self._seq_order(seed) if self.sequences else None
        segs = []
        for k in range(self.segments):
            rng = np.random.default_rng(_seed_seq(seed, r, k))
            if order is not None:
                b_types, b_bytes = self._sequences[order[(r * self.segments + k) % len(order)]]
            else:
                pick = rng.permutation(np.repeat(np.arange(len(self.mix)), self._body_counts()))
                b_types, b_bytes = self._mix[0][pick], self._mix[1][pick]
            types = np.concatenate([self._prefix[0], b_types])
            n = types.size
            q = (np.arange(n) + 0.5) / n
            gaps = rng.permutation(-np.log1p(-q) / self.rate)
            t = np.cumsum(gaps)
            segs.append(Segment(
                time=t - t[0],
                rs=grid.TYPE_RS[types], fs=grid.TYPE_FS[types],
                nbytes=np.concatenate([self._prefix[1], b_bytes]),
                wtype=types))
        return Round(r, tuple(segs))

    def rounds(self, seed: int, start: int, count: int) -> list[Round]:
        return [self.round(seed, r) for r in range(start, start + count)]


def _typed(tuples) -> tuple[np.ndarray, np.ndarray]:
    """(grid types, bytes of one pass over the file) of (rs, fs) tuples."""
    return (np.array([grid.type_of(rs, fs) for rs, fs in tuples], np.int32).reshape(-1),
            np.array([fs for _, fs in tuples], np.float64).reshape(-1))


def _seed_seq(seed: int, *keys: int) -> np.random.SeedSequence:
    """Entropy from any whole-number seed (negative and > 64 bits too)."""
    s = int(seed)
    words = [abs(s) & 0xFFFFFFFF, (abs(s) >> 32) & 0xFFFFFFFF, int(s < 0)]
    return np.random.SeedSequence(words + [k + 1 for k in keys])
