"""Analytical end-to-end LLM inference simulator (paper §VI-D, Figs. 1/14).

Follows the framework of Chen et al. [7]: transformer decode is alternating
memory phases (weight streaming from HBM) and compute phases (MAC-array
limited), with idealized streaming and on-chip activation reuse:

    t_layer = max( weight_bytes / BW,  batch * MACs / (units * freq) )

The MAC-unit count is the resource-budget quotient over the *per-operation*
LUT/FF/DSP cost of the arithmetic unit — which is exactly where XtraMAC's
density advantage (Table IV/V) enters: same fabric, more MAC lanes.  The
baseline instantiates the AMD FP-Operator profiles; XtraMAC swaps in its
per-lane costs.  Everything else (checkpoint MAC counts, datatype split,
tiling) is held fixed, so Fig. 14's deltas isolate arithmetic-unit density.

MAC counting per decode token (context L):
  projections/FFN (quantized):  2 * N_proj_params   MACs  (scheme datatype)
  attention QK^T + PV (BF16):   2 * 2 * L * H * dh * n_layers
  MoE: only top-k (+shared) expert params are active.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, Optional, Tuple

from repro.core.gemv_engine import GemvEngineConfig
from repro.core.resource_model import Resources, TABLE_IV, TABLE_V
from repro.models.transformer import ModelConfig
from .hardware import FPGAProfile, V80


# Every deployment must execute BOTH the scheme's quantized MACs
# (projections/FFN) and BF16 MACs (attention) at runtime.  The vendor
# baseline does this by SPATIAL REPLICATION (both datapaths instantiated
# per slot — Fig. 2b); XtraMAC shares ONE runtime-switching instance
# (Table III) whose lanes serve both phases.
#
# scheme -> (vendor per-slot = quant IP + BF16 IP,
#            xtramac switching instance, quant lanes, bf16 lanes)
from repro.core.resource_model import TABLE_III

_VENDOR_BF16 = TABLE_V["vendor"]["bf16"]                      # 220/310.5/1

# vendor slot / (quant lanes, bf16 lanes) per slot:
#  * FP-accumulate schemes: ONE upcast FP datapath (Table IV vendor row,
#    conversion module included) serves both phases at 1 lane each.
#  * W8A8 (INT32 accumulate): the FP operator cannot absorb INT8 — the
#    vendor deploys spatial replication (2-lane INT8 MAC + BF16 MAC).
_DEPLOY = {
    "awq_int4": (TABLE_IV[("int8", "bf16")][0], (1, 1),
                 TABLE_III["I:int4xbf16+bf16"], (2, 2)),
    "w8a8": (TABLE_V["vendor"]["int8"].scale(2) + _VENDOR_BF16, (2, 1),
             TABLE_III["II:int8xint8+int32|bf16"], (2, 2)),
    "fp8": (TABLE_IV[("fp8_e4m3", "bf16")][0], (1, 1),
            TABLE_III["III:fp8xfp8+bf16|bf16"], (4, 2)),
    "mxfp4": (TABLE_IV[("fp4_e2m1", "bf16")][0], (1, 1),
              TABLE_III["IV:fp4xbf16+bf16|bf16"], (2, 2)),
}

_SCHEME_WEIGHT_BITS = {"awq_int4": 4, "mxfp4": 4, "fp8": 8, "w8a8": 8,
                       "bf16": 16}


def gemv_engine_for(scheme: str, fpga: FPGAProfile = V80) -> GemvEngineConfig:
    """Datatype-adaptive MAC engine for ``scheme`` on ``fpga``: the
    channel-streaming GEMV model of ``core/gemv_engine.py`` (paper §VI-C)
    with the lane count set by the scheme's weight precision —
    ``N_MAC = channel_bits / (w_bits * P)`` — and the profile's HBM
    bandwidth and power.  A 4-bit scheme packs 4x the MAC lanes of bf16
    into the same channels, so pricing through this engine makes compute
    cost *per-datatype* rather than a flat MAC count at a fixed rate.
    The channel geometry (30 active 512-bit channels) is the paper's
    U55c layout; only bandwidth/power scale with the profile."""
    return GemvEngineConfig(
        hbm_bw_gbps=fpga.hbm_gbps, power_w=fpga.power_w,
        weight_bits=min(_SCHEME_WEIGHT_BITS[scheme], 16))


@functools.lru_cache(maxsize=64)
def _param_split(cfg: ModelConfig) -> Dict[str, float]:
    """Active parameter counts by role: {'proj': N, 'head': N} per layer sum.
    Memoized (ModelConfig is frozen/hashable): the SLO planner calls
    ``decode_latency`` once per distinct step shape and the abstract
    param-tree walk is the dominant cost of each call."""
    from repro.launch.roofline import model_params
    p = model_params(cfg)
    # embedding + lm_head stream once per token too, in bf16
    emb = cfg.vocab * cfg.d_model * (1 if cfg.tie_embeddings else 2)
    return {"proj": p["active"] - emb, "emb": float(emb)}


def mac_distribution(cfg: ModelConfig, scheme: str, context: int
                     ) -> Dict[str, float]:
    """Fig. 1: fraction of decode MACs per datatype combination."""
    split = _param_split(cfg)
    proj_macs = split["proj"] + split["emb"] * 0  # embeddings: lookup, no MAC
    lm_head_macs = cfg.vocab * cfg.d_model
    attn_macs = 2.0 * context * cfg.n_heads * cfg.head_dim * cfg.n_layers
    total = proj_macs + lm_head_macs + attn_macs
    combos = {
        "awq_int4": "INT4xBF16", "mxfp4": "FP4xBF16",
        "fp8": "FP8xFP8", "w8a8": "INT8xINT8", "bf16": "BF16xBF16",
    }
    quant_name = combos[scheme]
    dist = {quant_name: proj_macs / total}
    dist["BF16xBF16"] = dist.get("BF16xBF16", 0.0) + \
        (attn_macs + lm_head_macs) / total
    return dist


def mac_unit_budget(per_op: Resources, fpga: FPGAProfile) -> int:
    """How many MAC lanes the fabric budget supports."""
    lut_lim = fpga.usable_fraction * fpga.luts / max(per_op.lut, 1e-9)
    ff_lim = fpga.usable_fraction * fpga.ffs / max(per_op.ff, 1e-9)
    dsp_lim = fpga.usable_fraction * fpga.dsps / max(per_op.dsp, 1e-9)
    return int(min(lut_lim, ff_lim, dsp_lim))


def decode_latency(cfg: ModelConfig, scheme: str, *, batch: int, context: int,
                   design: str, fpga: FPGAProfile = V80,
                   kv_bytes_per_token: float = None,
                   engine_model: Optional[GemvEngineConfig] = None
                   ) -> Dict[str, float]:
    """One decode step latency under the two-phase streaming model.

    ``kv_bytes_per_token`` overrides the default bf16 KV storage cost
    (2 slabs x 2 B x Hk x dh x L per cached position) — quantized KV
    tiers (DESIGN.md §9) stream fewer bytes per context position, which
    is how a pool tier is priced into the prediction.

    ``engine_model`` routes the compute phase through the channel-
    streaming GEMV engine (``gemv_engine_for``) instead of the fabric
    unit-budget tables: the quantized projections run at the engine's
    lane count for the scheme's weight bits, attention at the (4x
    sparser) bf16 lane count, and the memory phase is derated by the
    engine's measured HBM utilization.  This is the per-datatype MAC
    pricing ``serve/slo.py`` plans with; the Fig. 14
    vendor-vs-XtraMAC comparison keeps the table-budget path
    (``engine_model=None``) so its density deltas stay isolated.
    """
    split = _param_split(cfg)
    w_bits = _SCHEME_WEIGHT_BITS[scheme]
    weight_bytes = split["proj"] * w_bits / 8.0 + split["emb"] * 2.0
    # KV read for attention, grows with context (default: bf16 storage)
    if kv_bytes_per_token is None:
        kv_bytes_per_token = \
            2.0 * 2 * cfg.n_kv_heads * cfg.head_dim * cfg.n_layers
    kv_bytes = context * float(kv_bytes_per_token)
    bw = fpga.hbm_gbps * 1e9
    if engine_model is not None:
        bw *= engine_model.hbm_utilization
    t_mem = (weight_bytes + batch * kv_bytes) / bw

    proj_macs = split["proj"] + cfg.vocab * cfg.d_model
    attn_macs = 2.0 * context * cfg.n_heads * cfg.head_dim * cfg.n_layers
    if engine_model is not None:
        eng_q = dataclasses.replace(engine_model,
                                    weight_bits=min(w_bits, 16))
        eng_b = dataclasses.replace(engine_model, weight_bits=16)
        units_q, units_b = eng_q.macs_per_cycle, eng_b.macs_per_cycle
        t_compute = batch * (
            proj_macs / (units_q * eng_q.freq_hz)
            + attn_macs / (units_b * eng_b.freq_hz))
    else:
        vendor_slot, (vq, vb), xtra_inst, (xq, xb) = _DEPLOY[scheme]
        if design == "vendor":
            slots = mac_unit_budget(vendor_slot, fpga)
            units_q, units_b = slots * vq, slots * vb
        else:
            slots = mac_unit_budget(xtra_inst, fpga)
            units_q, units_b = slots * xq, slots * xb
        freq = fpga.freq_mhz * 1e6
        t_compute = batch * (proj_macs / (units_q * freq)
                             + attn_macs / (units_b * freq))
    return {"t_mem_s": t_mem, "t_compute_s": t_compute,
            "t_total_s": max(t_mem, t_compute),
            "bound": "memory" if t_mem >= t_compute else "compute",
            "units_quant": units_q, "units_bf16": units_b}


def spec_round_latency(cfg: ModelConfig, *, k: int, batch: int, context: int,
                       design: str = "xtramac",
                       draft_scheme: str = "awq_int4",
                       target_scheme: str = "w8a8",
                       acceptance: float = 0.7,
                       kv_bytes_per_token: float = None,
                       draft_kv_bytes_per_token: float = None,
                       fpga: FPGAProfile = V80,
                       use_engine_model: bool = True) -> Dict[str, float]:
    """Price one speculative decode round (DESIGN.md §17): K draft steps
    at the aggressive scheme/KV tier plus ONE (K+1)-position verify
    dispatch at the target precision — the draft/verify pair the serving
    scheduler issues, so SLO admission can stay honest about speculative
    throughput.

    The verify dispatch streams the target weights and KV exactly ONCE
    (its memory phase equals a plain decode step's) while its compute
    phase covers K+1 positions per row — so the window rides along at
    ~one plain step's cost exactly where the MAC array has idle compute
    headroom (the Table-III/IV slot deployment at small batch), and
    costs linearly per position on the channel-streaming GEMV engine,
    whose lanes are throughput-matched to HBM by construction.  The
    model reports whichever bound holds; speculation wins wall clock
    only in the headroom regime.  (This prices the DEPLOYMENT's
    single-weight-stream verify; the host engine scores the window as
    chained exact decode steps inside the one dispatch for bit-identity
    — see ``serve/engine.py`` ``verify_slots``.)

    ``acceptance`` is the per-position draft acceptance rate a; expected
    emitted tokens per row per round is the geometric sum
    E = (1 - a^(K+1)) / (1 - a)  (every round emits at least the verify's
    own position-0 sample).  Returns the round wall, the effective
    per-token latency t_round / E, the plain-decode per-token latency at
    the target precision, and their ratio (> 1 = speculation wins)."""
    assert k >= 1 and 0.0 <= acceptance < 1.0
    eng_d = gemv_engine_for(draft_scheme, fpga) if use_engine_model else None
    eng_t = gemv_engine_for(target_scheme, fpga) if use_engine_model else None
    draft = decode_latency(
        cfg, draft_scheme, batch=batch, context=context, design=design,
        fpga=fpga, kv_bytes_per_token=draft_kv_bytes_per_token,
        engine_model=eng_d)
    target = decode_latency(
        cfg, target_scheme, batch=batch, context=context, design=design,
        fpga=fpga, kv_bytes_per_token=kv_bytes_per_token,
        engine_model=eng_t)
    t_draft = k * draft["t_total_s"]
    t_verify = max(target["t_mem_s"], (k + 1) * target["t_compute_s"])
    t_round = t_draft + t_verify
    a = acceptance
    e_tokens = (1.0 - a ** (k + 1)) / (1.0 - a) if a > 0 else 1.0
    t_plain = target["t_total_s"]
    return {
        "t_draft_s": t_draft, "t_verify_s": t_verify,
        "t_round_s": t_round,
        "expected_tokens_per_row": e_tokens,
        "t_per_token_s": t_round / e_tokens,
        "t_plain_per_token_s": t_plain,
        "speedup": t_plain / (t_round / e_tokens),
        "verify_bound": "memory"
        if target["t_mem_s"] >= (k + 1) * target["t_compute_s"]
        else "compute",
    }


def fig14_simulation(context: int = 512, batches=(1, 8, 32),
                     fpga: FPGAProfile = V80) -> Dict:
    """Reproduce Fig. 14: per-checkpoint decode latency, vendor vs XtraMAC."""
    from repro.configs.xtramac_paper import PAPER_CHECKPOINTS
    rows = {}
    for name, (cfg, scheme) in PAPER_CHECKPOINTS.items():
        per_batch = {}
        for b in batches:
            v = decode_latency(cfg, scheme, batch=b, context=context,
                               design="vendor", fpga=fpga)
            x = decode_latency(cfg, scheme, batch=b, context=context,
                               design="xtramac", fpga=fpga)
            per_batch[b] = {
                "vendor_ms": v["t_total_s"] * 1e3,
                "xtramac_ms": x["t_total_s"] * 1e3,
                "speedup": v["t_total_s"] / x["t_total_s"],
                "bound": x["bound"],
            }
        rows[name] = per_batch
    return rows
