"""The work a configuration's serving steps need, from its sizes alone.

Counted for the algorithm, whatever implements it, so that a change of
kernel or of fusion is measured against the same yardstick:

* bytes of one decode step (all rows advance one token): every layer's
  packed weight codes and scales, the bf16 lm_head, the norm gains, and per
  row one embedding row, a read of every live K/V position and one K/V
  write, at the pool's bytes per token;
* FLOPs: 2 per weight a row touches (every layer and the lm_head) and, per
  layer, 4 * live length * n_heads * d_head for the scores and the values.

``peak(device_kind)`` reads the peaks table (``peaks.json``); a device that
is not in it is an error.
"""
from __future__ import annotations

import json
import os
from typing import Dict

_BITS = {"int4": 4}
_SCALE_BYTES = {"f32": 4}


def peak(device_kind: str) -> Dict:
    with open(os.path.join(os.path.dirname(__file__), "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}")
    return table[device_kind]


def layer_linears(m: Dict) -> Dict[str, tuple]:
    """(K, N) of each linear of one layer, keyed attention / FFN (gated:
    gate, up and down)."""
    if not m["gated_ffn"]:
        raise ValueError("only gated FFNs are counted")
    d, h, hk, dh, f = (m["d_model"], m["n_heads"], m["n_kv_heads"],
                       m["d_head"], m["d_ff"])
    attn = [(d, h * dh), (d, hk * dh), (d, hk * dh), (h * dh, d)]
    return {"proj": attn, "ffn": [(d, f), (d, f), (f, d)]}


def weight_bytes(cfg_file: Dict) -> float:
    """Bytes one decode step must read of the weights."""
    m, w = cfg_file["model"], cfg_file["weights"]
    total = 0.0
    for kind, mats in layer_linears(m).items():
        fmt = w[kind]
        for k, n in mats:
            total += k * n * _BITS[fmt["format"]] / 8
            total += k // fmt["group"] * n * _SCALE_BYTES[fmt["scale"]]
    total *= m["n_layers"]
    total += m["d_model"] * m["vocab"] * 2            # bf16 lm_head
    total += (2 * m["n_layers"] + 1) * m["d_model"] * 4   # f32 norm gains
    return total


def kv_bytes_per_token(cfg_file: Dict, kv: str = "bf16") -> float:
    m = cfg_file["model"]
    if kv != "bf16":
        raise ValueError(f"no byte count for KV tier {kv!r}")
    return m["n_layers"] * 2 * m["n_kv_heads"] * m["d_head"] * 2


def weight_flops_per_token(cfg_file: Dict, with_head: bool = True) -> float:
    m = cfg_file["model"]
    n = sum(k * n for mats in layer_linears(m).values() for k, n in mats)
    return 2.0 * (n * m["n_layers"]
                  + (m["d_model"] * m["vocab"] if with_head else 0))


def attn_flops(cfg_file: Dict, live: float) -> float:
    """Scores and values of one token attending ``live`` positions."""
    m = cfg_file["model"]
    return 4.0 * live * m["n_heads"] * m["d_head"] * m["n_layers"]


def decode_step(cfg_file: Dict, lives) -> Dict[str, float]:
    """FLOPs and bytes of one decode step whose rows attend ``lives``
    positions each (their new position included)."""
    m = cfg_file["model"]
    kvb = kv_bytes_per_token(cfg_file)
    rows = len(lives)
    flops = rows * weight_flops_per_token(cfg_file) \
        + sum(attn_flops(cfg_file, n) for n in lives)
    nbytes = weight_bytes(cfg_file) + rows * m["d_model"] * 2 \
        + sum(lives) * kvb + rows * kvb
    return {"flops": flops, "bytes": nbytes}


def prefill_token_flops(cfg_file: Dict, position: int,
                        last: bool) -> float:
    """FLOPs a prompt token at ``position`` needs (the lm_head only for the
    prompt's last token, whose logits give the first output token)."""
    return weight_flops_per_token(cfg_file, with_head=last) \
        + attn_flops(cfg_file, position + 1)


def roofline_seconds(cost: Dict[str, float], pk: Dict) -> float:
    """The least time the chip could take: the larger of FLOPs over the
    bf16 peak and bytes over the HBM bandwidth."""
    return max(cost["flops"] / pk["bf16_flops_per_s"],
               cost["bytes"] / pk["hbm_bytes_per_s"])
