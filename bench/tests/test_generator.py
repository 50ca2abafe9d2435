"""The traffic generator: fixed work per seed, only its order changes."""
from collections import Counter
from pathlib import Path

import numpy as np
import pytest

from bench import grid
from bench.generator import Mix

TRAFFIC = Path(__file__).resolve().parents[1] / "traffic"
MIXES = sorted(p.stem for p in TRAFFIC.glob("*.json"))


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_rounds_and_fixed_shape(name):
    mix = Mix.load(TRAFFIC / f"{name}.json")
    a, b = mix.round(2**31 + 7, 5), mix.round(2**31 + 7, 5)
    for sa, sb in zip(a.segments, b.segments):
        assert np.array_equal(sa.wtype, sb.wtype) and np.array_equal(sa.time, sb.time)
    assert len(a.segments) == mix.segments
    assert all(s.time.size == mix.per_segment for s in a.segments)
    assert all(s.time[0] == 0.0 and np.all(np.diff(s.time) > 0) for s in a.segments)


@pytest.mark.parametrize("name", MIXES)
def test_seeds_share_the_work_in_another_order(name):
    """Every segment of every seed holds the same gaps, and per type the
    weights' share of the body to within one task."""
    mix = Mix.load(TRAFFIC / f"{name}.json")
    ref_gaps = None
    for seed in (1, 2**33 + 1, -5):
        for rnd in mix.rounds(seed, 0, 3):
            for seg in rnd.segments:
                gaps = np.sort(np.diff(seg.time))
                if ref_gaps is None:
                    full = -np.log1p(-(np.arange(mix.per_segment) + 0.5)
                                     / mix.per_segment) / mix.rate
                    ref_gaps = full
                # n quantile gaps, shuffled; the first is dropped by the
                # shift to the segment's start
                assert np.all(np.isin(np.round(gaps * mix.rate, 9),
                                      np.round(ref_gaps * mix.rate, 9)))
                if mix.mix:
                    w = np.array([x[2] for x in mix.mix])
                    share = mix.body * w / w.sum()
                    types = [grid.type_of(rs, fs) for rs, fs, _ in mix.mix]
                    body = Counter(seg.wtype[len(mix.prefix):].tolist())
                    for t in set(types):
                        want = share[[i for i, x in enumerate(types) if x == t]].sum()
                        assert abs(body[t] - want) < 1.0 + 1e-9 * want + sum(
                            1 for x in types if x == t)


def test_table3_segments_are_residents_then_a_sequence():
    mix = Mix.load(TRAFFIC / "table3.json")
    r = mix.round(9, 0)
    residents = [grid.type_of(rs, fs) for rs, fs in mix.prefix]
    seqs = {tuple(grid.type_of(rs, fs) for rs, fs in s) for s in mix.sequences}
    for seg in r.segments:
        assert seg.wtype[:12].tolist() == residents
        assert tuple(seg.wtype[12:].tolist()) in seqs
    assert mix.per_segment == 17


def test_grid_snaps_in_log_space():
    assert grid.T == 230
    assert grid.type_of(8 * 1024, 3 * 1024**2) == grid.type_of(8 * 1024, 4 * 1024**2)
    assert grid.TYPE_FS[grid.type_of(64 * 1024, 64 * 1024**2)] == 64 * 1024**2
