"""The work a configuration's serving steps need, from its sizes alone.

Counted for the algorithm, whatever implements it, so that a change of
kernel or of fusion is measured against the same yardstick:

* bytes of one decode step (all rows advance one token): every layer's
  packed weight codes and scales, the bf16 lm_head, the norm gains, and per
  row one embedding row, a read of every live K/V position and one K/V
  write, at the pool's bytes per token;
* FLOPs: 2 per weight a row touches (every layer and the lm_head) and, per
  layer, 4 * live length * n_heads * d_head for the scores and the values.

A layer's linears are the architecture's: the configuration's reference
module lists them (its ``linears``).  What one read of a linear costs is
its weight format's (``formats/<format>.py``, its ``nbytes``).  Both are
looked up under ``bench_dir`` (None: this benchmark's directory).

``peak(device_kind)`` reads the peaks table (``peaks.json``); a device that
is not in it is an error.
"""
from __future__ import annotations

import json
import os
from typing import Dict, Optional

from . import spec


def peak(device_kind: str) -> Dict:
    with open(os.path.join(os.path.dirname(__file__), "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}")
    return table[device_kind]


def linear_cost(cfg_file: Dict, bench_dir: Optional[str] = None
                ) -> Dict[str, int]:
    """Weights of one layer's linears, as the configuration's reference
    module lists them, and the bytes one read of them costs in the weight
    formats the file states."""
    bench_dir = bench_dir or spec.BENCH_DIR
    ref = spec.load_module("references", cfg_file["reference"], bench_dir)
    w = cfg_file["weights"]
    weights = nbytes = 0
    for kind, mats in ref.linears(cfg_file["model"]).items():
        for k, n in mats.values():
            fmt = spec.layer_format(w[kind], k)
            rules = spec.load_module("formats", fmt["format"], bench_dir)
            if rules is None:
                raise KeyError(f"no byte count for weights of format "
                               f"{w[kind]}: no formats/{fmt['format']}.py")
            weights += k * n
            nbytes += rules.nbytes(k, n, fmt)
    return {"weights": weights, "bytes": nbytes}


def weight_bytes(cfg_file: Dict, bench_dir: Optional[str] = None) -> float:
    """Bytes one decode step must read of the weights."""
    m = cfg_file["model"]
    total = float(linear_cost(cfg_file, bench_dir)["bytes"] * m["n_layers"])
    total += m["d_model"] * m["vocab"] * 2            # bf16 lm_head
    total += (2 * m["n_layers"] + 1) * m["d_model"] * 4   # f32 norm gains
    return total


def kv_bytes_per_token(cfg_file: Dict, kv: str = "bf16") -> float:
    m = cfg_file["model"]
    if kv != "bf16":
        raise ValueError(f"no byte count for KV tier {kv!r}")
    return m["n_layers"] * 2 * m["n_kv_heads"] * m["d_head"] * 2


def weight_flops_per_token(cfg_file: Dict, with_head: bool = True,
                           bench_dir: Optional[str] = None) -> float:
    m = cfg_file["model"]
    n = linear_cost(cfg_file, bench_dir)["weights"]
    return 2.0 * (n * m["n_layers"]
                  + (m["d_model"] * m["vocab"] if with_head else 0))


def attn_flops(cfg_file: Dict, live: float) -> float:
    """Scores and values of one token attending ``live`` positions."""
    m = cfg_file["model"]
    return 4.0 * live * m["n_heads"] * m["d_head"] * m["n_layers"]


def decode_step(cfg_file: Dict, lives, bench_dir: Optional[str] = None
                ) -> Dict[str, float]:
    """FLOPs and bytes of one decode step whose rows attend ``lives``
    positions each (their new position included)."""
    m = cfg_file["model"]
    kvb = kv_bytes_per_token(cfg_file)
    rows = len(lives)
    flops = rows * weight_flops_per_token(cfg_file, bench_dir=bench_dir) \
        + sum(attn_flops(cfg_file, n) for n in lives)
    nbytes = weight_bytes(cfg_file, bench_dir) + rows * m["d_model"] * 2 \
        + sum(lives) * kvb + rows * kvb
    return {"flops": flops, "bytes": nbytes}


def prefill_token_flops(cfg_file: Dict, position: int, last: bool,
                        bench_dir: Optional[str] = None) -> float:
    """FLOPs a prompt token at ``position`` needs (the lm_head only for the
    prompt's last token, whose logits give the first output token)."""
    return weight_flops_per_token(cfg_file, last, bench_dir) \
        + attn_flops(cfg_file, position + 1)


def roofline_seconds(cost: Dict[str, float], pk: Dict) -> float:
    """The least time the chip could take: the larger of FLOPs over the
    bf16 peak and bytes over the HBM bandwidth."""
    return max(cost["flops"] / pk["bf16_flops_per_s"],
               cost["bytes"] / pk["hbm_bytes_per_s"])
