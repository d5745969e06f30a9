"""Views of a run's record that several per-layer readers share."""
from __future__ import annotations


def window_steps(rec):
    """The steps that returned inside the measured window."""
    return [s for s in rec["steps"] if s["t"] <= rec["t_end"] - rec["t0"]]


def decode_substeps(rec):
    """Live lengths of the rows of every decode sub-step in the window: a
    step that delivered request r's tokens j0..j0+n-1 (j >= 1 are decode
    tokens) ran sub-step m for r at live length prompt + j."""
    out = []
    for st in window_steps(rec):
        per_row = []
        for plen, before, n in st["rows"]:
            first = max(before, 1)
            per_row.append([plen + j for j in range(first, before + n)])
        depth = max((len(r) for r in per_row), default=0)
        for m in range(depth):
            out.append([r[m] for r in per_row if len(r) > m])
    return out
