"""The paper's profiling grid (Sec. IV.B, VIII) and Table III tuple parsing.

The benchmark's own copy: the traffic generator and the reference read it,
so neither depends on the program for what a workload type is. Ten request
sizes (1 KB - 512 KB) by 23 file sizes (1 KB - 1 GB plus the 6 MB LLC edge
and 448 MB file-cache edge), 230 read types, type = rs_index * 23 + fs_index.
"""
from __future__ import annotations

import re

import numpy as np

KB = 1024.0
MB = 1024.0 * KB
GB = 1024.0 * MB

RS_GRID = np.array([KB * 2**i for i in range(10)])
FS_GRID = np.array(sorted({KB * 2**i for i in range(21)} | {6 * MB, 448 * MB}))
T = RS_GRID.size * FS_GRID.size  # 230

#: every type's (rs, fs), in type order
TYPE_RS = np.repeat(RS_GRID, FS_GRID.size)
TYPE_FS = np.tile(FS_GRID, RS_GRID.size)

_UNITS = (("KB", KB), ("MB", MB), ("GB", GB), ("B", 1.0))
_TUPLE = re.compile(r"\(\s*([^,()]+)\s*,\s*([^,()]+)\s*\)")


def parse_size(text: str) -> float:
    """'32KB' -> 32768.0 (binary units, as the paper's tables)."""
    s = text.strip().upper().replace(" ", "")
    for suffix, mult in _UNITS:
        if s.endswith(suffix):
            return float(s[: -len(suffix)]) * mult
    return float(s)


def parse_tuples(text: str) -> list[tuple[float, float]]:
    """'(RS, FS), (RS, FS)' -> [(rs, fs), ...] in bytes, request size first."""
    out = [(parse_size(a), parse_size(b)) for a, b in _TUPLE.findall(text)]
    if not out:
        raise ValueError(f"no (RS, FS) tuples in {text!r}")
    return out


def type_of(rs: float, fs: float) -> int:
    """Nearest grid type in log space (the paper's snapping of a tuple)."""
    ri = int(np.argmin(np.abs(np.log(RS_GRID) - np.log(rs))))
    fi = int(np.argmin(np.abs(np.log(FS_GRID) - np.log(fs))))
    return ri * FS_GRID.size + fi
