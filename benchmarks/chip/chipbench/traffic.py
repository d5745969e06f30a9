"""One general generator for every traffic mix, driven by its data file.

The traffic is a closed loop: one client per slot, each sending its next
request when the last one finishes.  The length law follows the serving
benchmark the repository already had (``benchmarks/serve_bench.py``:
clipped-Pareto lengths), copied here so that the yardstick cannot move with
the program.  Two changes make the runs of a cell comparable across seeds:

* Lengths are not drawn at random but taken at fixed
  quantiles of their law, ``block`` of them at a time, in bit-reversed
  order (0, 8, 4, 12, 2, ... of 16), so that any run of consecutive
  requests spreads over the quantiles.  That order is the same for every
  seed: a window admits only a few requests, and which sizes they bring
  sets how much of it goes to prefill, so a seed that moved them would
  change the work.  The seed draws the token ids (and the weights): every
  seed offers the same work on other inputs.
* The loop starts from the steady state: the first fill gives each
  client a request whose output budget is drawn from the residual law of
  the output lengths (the tokens a busy slot still has to go), so requests
  finish at the steady rate from the start of the window.

A mix file holds:

    block       quantiles per block of the stream
    prompt      {"law": "pareto", "alpha", "scale", "min", "max"}
    output      the same, for the output budget (max_new_tokens)
"""
from __future__ import annotations

from typing import Dict, List, Tuple

import numpy as np


def quantiles(law: Dict, n: int) -> np.ndarray:
    """``n`` lengths at the mid-quantiles (i + 1/2) / n of a clipped
    Pareto law: scale * (1 - u) ** (-1 / alpha), clipped to [min, max]."""
    if law["law"] != "pareto":
        raise ValueError(f"unknown length law {law['law']!r}")
    u = (np.arange(n) + 0.5) / n
    x = law["scale"] * (1.0 - u) ** (-1.0 / law["alpha"])
    return np.clip(np.floor(x), law["min"], law["max"]).astype(np.int64)


def spread_order(n: int) -> np.ndarray:
    """0..n-1 in bit-reversed order (of the next power of two, keeping the
    indices below n): consecutive entries fall far apart, so every run of
    them samples the whole range."""
    bits = max(1, (n - 1).bit_length())
    rev = [int(format(i, f"0{bits}b")[::-1], 2) for i in range(1 << bits)]
    return np.array([i for i in rev if i < n])


def residual_quantiles(outputs: np.ndarray, n: int) -> np.ndarray:
    """``n`` remaining output budgets at the mid-quantiles of the residual
    law of ``outputs``: a slot picked at a random time holds a request of
    length L with probability proportional to L, and has a uniform share of
    it still to go.  P(R = r) is proportional to #{L >= r}."""
    rs = np.sort(np.concatenate([np.arange(1, int(v) + 1) for v in outputs]))
    u = (np.arange(n) + 0.5) / n
    return rs[np.minimum((u * rs.size).astype(np.int64), rs.size - 1)]


class Traffic:
    """The request stream of one mix and one seed.

    ``next()`` yields (prompt int32 [P], max_new): the prompt's tokens and
    its output budget.  Requests come in blocks, the quantile values of
    each in the spread order; the seed draws the token ids."""

    def __init__(self, mix: Dict, seed: int, vocab: int):
        self.mix = mix
        self.block = int(mix["block"])
        self.vocab = int(vocab)
        self.rng = np.random.default_rng(int(seed))
        self.outputs = quantiles(mix["output"], self.block)
        order = spread_order(self.block)
        self._sizes = list(zip(quantiles(mix["prompt"], self.block)[order],
                               self.outputs[order]))
        self._queue: List[Tuple[np.ndarray, int]] = []

    @property
    def max_len(self) -> int:
        """Slot capacity the mix needs: longest prompt + longest output."""
        return int(self.mix["prompt"]["max"] + self.mix["output"]["max"])

    def next(self) -> Tuple[np.ndarray, int]:
        if not self._queue:
            self._queue.extend(
                (self.rng.integers(1, self.vocab, int(n)).astype(np.int32),
                 int(m)) for n, m in self._sizes)
        return self._queue.pop(0)

    def fill(self, n: int) -> List[Tuple[np.ndarray, int]]:
        """The ``n`` requests that occupy the clients when the
        window opens — prompts from the stream, output budgets from the
        residual law, in the spread order."""
        rest = residual_quantiles(self.outputs, n)[spread_order(n)]
        return [(self.next()[0], int(r)) for r in rest]
