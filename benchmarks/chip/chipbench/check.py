"""Decide ``correct``: the served tokens against the plain reference.

Once the window has closed and the program's state is freed, a sample of
the requests the run finished — drawn from the seed, with the longest of
them always in it — is run through the configuration's plain reference,
each prompt followed by the tokens the program served for it.  For every
served token the reference gives the gap by which that token's logit lies
below the reference's best logit at that position (0 when the reference
would have chosen it too).  Served tokens are greedy, so a sound program
only trails the reference's best by its rounding on near-ties.

The number compared is the mean of these gaps over the served tokens.
Their widest gap is printed too, but not held to a limit: over a few
hundred tokens it swings from seed to seed so far that a lower precision
(the control) and sound runs overlap within a factor of three, while the
mean separates them (see PERF.md).

How many requests are compared is the cell's (``rows`` in its limits
file).  The reference runs them in blocks of ``BATCH`` sequences padded to
the cell's slot capacity, so it compiles once per cell.
"""
from __future__ import annotations

from typing import Dict, List

import numpy as np

from . import spec

BATCH = 4           # sequences the reference runs at once


def sample(finished: List, seed: int, rows: int) -> List:
    """The longest finished request, then others drawn from the seed, up to
    ``rows`` requests."""
    if not finished:
        return []
    order = sorted(finished, key=lambda s: (-(s.req.prompt_len
                                              + s.req.n_generated),
                                            s.req.id))
    picked = [order[0]]
    rest = order[1:]
    rng = np.random.default_rng(int(seed) + 1)
    for i in rng.permutation(len(rest)):
        if len(picked) >= rows:
            break
        picked.append(rest[i])
    return picked


def batch(picked: List, max_len: int):
    """Padded [BATCH, max_len] inputs and targets, and the mask of scored
    positions: each prompt followed by its served tokens; position p is
    scored when the token at p + 1 was served."""
    tokens = np.zeros((BATCH, max_len), np.int32)
    targets = np.zeros((BATCH, max_len), np.int32)
    mask = np.zeros((BATCH, max_len), bool)
    for b, s in enumerate(picked):
        out = np.asarray(s.req.output_tokens, np.int32)
        seq = np.concatenate([s.req.prompt, out])
        n = min(len(seq), max_len)
        tokens[b, :n - 1] = seq[:n - 1]
        p = s.req.prompt_len
        targets[b, p - 1:n - 1] = seq[p:n]
        mask[b, p - 1:n - 1] = True
    return tokens, targets, mask


def stats(gaps: np.ndarray) -> Dict[str, float]:
    """Mean and widest gap, and the share of tokens off the best."""
    return {"mean": float(gaps.mean()), "max": float(gaps.max()),
            "off_best": float(np.mean(gaps > 0))}


def compare(cfg_file: Dict, seed: int, finished: List, limits: Dict, *,
            max_len: int, bench_dir: str = spec.BENCH_DIR,
            controls=()) -> Dict:
    """Every number compared, with its limit, and whether all hold.

    ``controls`` (calibration only): lower precisions of the reference
    (``"fp8"``) to read as well — at each position of the same prompts and
    served tokens, the gap under the full reference of the token the lower
    precision puts first."""
    ref = spec.load_module("references", cfg_file["reference"], bench_dir)
    picked = sample(finished, seed, int(limits["rows"]))
    lim = limits["mean_logit_gap"]["limit"]
    if not picked:       # nothing finished: nothing shown correct
        return {"ok": False, "tokens": 0, "requests": 0, "gaps": {},
                "numbers": {"mean_logit_gap": {"value": None, "limit": lim}},
                "controls": {}}
    gaps, cgaps = [], {lower: [] for lower in controls}
    for i in range(0, len(picked), BATCH):
        tokens, targets, mask = batch(picked[i:i + BATCH], max_len)
        gap, _ = ref.logits_summary(cfg_file, seed, tokens, targets)
        gaps.append(gap[mask])
        for lower in controls:
            _, top = ref.logits_summary(cfg_file, seed, tokens, targets,
                                        lower=lower)
            cgap, _ = ref.logits_summary(cfg_file, seed, tokens, top)
            cgaps[lower].append(cgap[mask])
    g = stats(np.concatenate(gaps))
    return {"ok": bool(g["mean"] <= lim), "tokens": int(sum(map(len, gaps))),
            "requests": len(picked), "gaps": g,
            "numbers": {"mean_logit_gap": {"value": g["mean"], "limit": lim}},
            "controls": {"ref_" + k: verdict(stats(np.concatenate(v)), lim)
                         for k, v in cgaps.items()}}


def verdict(g: Dict[str, float], lim: float) -> Dict:
    """A control's readings with the verdict the check gives them: ``ok``
    false where its mean gap is over the limit, as a sound control's must
    be."""
    return dict(g, ok=bool(g["mean"] <= lim))
