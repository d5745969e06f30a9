"""Unified, config-driven model stack for every assigned architecture.

One ``ModelConfig`` describes dense / MoE (incl. MLA) / SSM (xLSTM) /
hybrid (Mamba2+shared-attention) / VLM (patch-stub) / audio (enc-dec,
frame-stub) families.  Parameters are built by the Maker walk in
``common.py`` — the same walk yields real weights, quantized weights,
ShapeDtypeStructs (dry-run) and PartitionSpecs (pjit), so structure,
quantization plan and sharding cannot drift.

Homogeneous layer stacks run under ``lax.scan`` with optional
``jax.checkpoint`` (remat) — keeping the HLO small enough to compile the
512-device production mesh and bounding activation memory.  Heterogeneous
stacks (xLSTM's 7:1 mLSTM:sLSTM pattern, Zamba2's shared attention every 6
Mamba blocks) scan over *groups* with the special block unrolled inside the
group body.

Caches: every family exposes ``init_cache`` (zeros or abstract specs) and
the same forward entry point serves train (cache=None), prefill (cache +
index 0) and decode (cache + running index) — the serving engine in
``serve/`` builds on exactly this.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from . import attention as A
from . import moe as MOE
from . import ssm as S
from .common import (AbstractMaker, InitMaker, Maker, PspecMaker, QuantMaker,
                     activate, apply_linear, layer_norm, rms_norm, shard_act,
                     sinusoidal_positions)


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                 # dense | moe | ssm | hybrid | vlm | audio
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    d_head: int = 0             # 0 -> d_model // n_heads
    activation: str = "silu"
    gated_ffn: bool = True
    norm: str = "rms"           # rms | layer
    rope_theta: float = 10000.0
    use_rope: bool = True
    tie_embeddings: bool = True
    use_bias: bool = False      # a bias on every attention and FFN linear
    # --- quantization plan (the paper's technique) ---
    scheme_proj: Optional[str] = None    # attention/ssm projection weights
    scheme_ffn: Optional[str] = None     # FFN / expert weights
    # --- MoE ---
    n_experts: int = 0
    top_k: int = 0
    n_shared_experts: int = 0
    moe_d_ff: int = 0           # per-expert hidden dim (0 -> d_ff)
    capacity_factor: float = 1.25
    # --- MLA (DeepSeek-V2) ---
    use_mla: bool = False
    q_lora: int = 0
    kv_lora: int = 0
    d_head_nope: int = 128
    d_head_rope: int = 64
    d_head_v: int = 128
    # --- SSM / hybrid ---
    ssm_state: int = 64
    ssm_d_head: int = 64
    ssm_expand: int = 2
    ssm_chunk: int = 128
    slstm_every: int = 8        # xlstm: every 8th block is sLSTM
    attn_every: int = 6         # zamba2: shared attn block every 6 mamba
    # --- frontends (stubs: precomputed embeddings arrive as inputs) ---
    n_patches: int = 0          # vlm: patch embeddings [B, n_patches, d]
    n_frames: int = 0           # audio: encoder frames [B, n_frames, d]
    encoder_layers: int = 0     # audio enc-dec split
    # --- execution ---
    remat: bool = True
    kv_chunk: int = 512
    logit_softcap: float = 0.0
    microbatches: int = 1   # gradient-accumulation splits of the train batch

    def __post_init__(self):
        if self.use_bias and (self.use_mla or self.n_experts):
            raise ValueError(f"{self.name}: use_bias covers GQA attention "
                             "and the dense FFN, not MLA or expert layers")

    @property
    def head_dim(self) -> int:
        return self.d_head or self.d_model // self.n_heads

    @property
    def supports_long_context(self) -> bool:
        return self.family in ("ssm", "hybrid")

    @property
    def is_encoder_decoder(self) -> bool:
        return self.family == "audio"

    def attn_cfg(self, causal=True, use_rope=None) -> A.AttnConfig:
        return A.AttnConfig(
            d_model=self.d_model, n_heads=self.n_heads,
            n_kv_heads=self.n_kv_heads, d_head=self.head_dim,
            rope_theta=self.rope_theta,
            use_rope=self.use_rope if use_rope is None else use_rope,
            causal=causal, qkv_scheme=self.scheme_proj, kv_chunk=self.kv_chunk,
            use_bias=self.use_bias)

    def mla_cfg(self) -> A.MLAConfig:
        return A.MLAConfig(
            d_model=self.d_model, n_heads=self.n_heads, q_lora=self.q_lora,
            kv_lora=self.kv_lora, d_head_nope=self.d_head_nope,
            d_head_rope=self.d_head_rope, d_head_v=self.d_head_v,
            rope_theta=self.rope_theta, qkv_scheme=self.scheme_proj,
            kv_chunk=self.kv_chunk)

    def moe_cfg(self) -> MOE.MoEConfig:
        return MOE.MoEConfig(
            d_model=self.d_model, d_ff=self.moe_d_ff or self.d_ff,
            n_experts=self.n_experts, top_k=self.top_k,
            n_shared_experts=self.n_shared_experts,
            shared_d_ff=0,  # default: n_shared * expert d_ff (DeepSeek-V2)
            capacity_factor=self.capacity_factor, activation=self.activation,
            scheme=self.scheme_ffn)

    def mamba_cfg(self) -> S.Mamba2Config:
        return S.Mamba2Config(
            d_model=self.d_model, d_state=self.ssm_state,
            d_head=self.ssm_d_head, expand=self.ssm_expand,
            chunk=self.ssm_chunk, scheme=self.scheme_proj)

    def mlstm_cfg(self) -> S.MLSTMConfig:
        return S.MLSTMConfig(d_model=self.d_model, n_heads=self.n_heads,
                             expand=self.ssm_expand, chunk=self.ssm_chunk,
                             scheme=self.scheme_proj)

    def slstm_cfg(self) -> S.SLSTMConfig:
        return S.SLSTMConfig(d_model=self.d_model, n_heads=self.n_heads,
                             scheme=self.scheme_proj)


# ---------------------------------------------------------------------------
# Norm helper (gamma-only RMS or gamma+beta LayerNorm)
# ---------------------------------------------------------------------------
def _norm_params(mk: Maker, cfg: ModelConfig, name: str, stack, dim=None):
    d = dim or cfg.d_model
    p = {"g": mk.norm(name, stack, d)}
    if cfg.norm == "layer":
        p["b"] = mk.vector(name + ".b", stack, d)
    return p


@jax.named_scope("norm")
def _apply_norm(cfg: ModelConfig, p, x):
    if cfg.norm == "layer":
        return layer_norm(x, p["g"], p["b"])
    return rms_norm(x, p["g"])


# ---------------------------------------------------------------------------
# Dense FFN
# ---------------------------------------------------------------------------
def _ffn_params(mk: Maker, cfg: ModelConfig, stack):
    d, f, s = cfg.d_model, cfg.d_ff, cfg.scheme_ffn
    mats = ({"gate": (d, f), "up": (d, f), "down": (f, d)} if cfg.gated_ffn
            else {"in": (d, f), "out": (f, d)})
    p = {}
    for w, (k, n) in mats.items():
        p["w_" + w] = mk.dense("ffn.w_" + w, stack, k, n, scheme=s)
        if cfg.use_bias:
            p["b_" + w] = mk.bias(f"ffn.w_{w}.b", stack, n)
    return p


def _ffn_linear(p, w: str, x):
    """x @ FFN linear ``w``, plus its bias if it has one."""
    return apply_linear(p["w_" + w], x, bias=p.get("b_" + w))


def _ffn_apply(cfg: ModelConfig, p, x):
    if cfg.gated_ffn:
        g = shard_act(_ffn_linear(p, "gate", x), "btf")
        u = shard_act(_ffn_linear(p, "up", x), "btf")
        h = (activate(cfg.activation, g.astype(jnp.float32))
             * u.astype(jnp.float32)).astype(jnp.bfloat16)
        return _ffn_linear(p, "down", h)
    # non-gated path: activation math stays bf16 — relu^2/gelu are stable in
    # bf16 and the f32 cast otherwise stacks f32 saved-residuals per layer
    h = activate(cfg.activation, shard_act(_ffn_linear(p, "in", x), "btf"))
    return _ffn_linear(p, "out", h.astype(jnp.bfloat16))


# ---------------------------------------------------------------------------
# Transformer blocks (attention + FFN/MoE)
# ---------------------------------------------------------------------------
def _tf_block_params(mk: Maker, cfg: ModelConfig, stack, *, causal=True,
                     cross=False):
    p = {"ln1": _norm_params(mk, cfg, "ln1", stack)}
    if cfg.use_mla:
        p["attn"] = A.mla_params(mk, cfg.mla_cfg(), stack)
    else:
        p["attn"] = A.attn_params(mk, cfg.attn_cfg(causal), stack)
    if cross:
        p["ln_x"] = _norm_params(mk, cfg, "ln_x", stack)
        p["xattn"] = A.cross_attn_params(mk, cfg.attn_cfg(False), stack)
    p["ln2"] = _norm_params(mk, cfg, "ln2", stack)
    if cfg.n_experts and not cross:          # decoder MoE only in LM families
        p["moe"] = MOE.moe_params(mk, cfg.moe_cfg(), stack)
    else:
        p["ffn"] = _ffn_params(mk, cfg, stack)
    return p


def _tf_block_apply(cfg: ModelConfig, p, x, *, cache=None, cache_index=None,
                    positions=None, enc=None, causal=True, moe_groups=None,
                    attend_local=False, page_table=None):
    """One transformer block.  Returns (x, new_cache, aux)."""
    with jax.named_scope("attn"):
        h = _apply_norm(cfg, p["ln1"], x)
        fwd, acfg = (A.mla_forward, cfg.mla_cfg()) if cfg.use_mla \
            else (A.gqa_forward, cfg.attn_cfg(causal))
        attn_out, new_cache = fwd(p["attn"], acfg, h, cache=cache,
                                  cache_index=cache_index, positions=positions,
                                  attend_local=attend_local,
                                  page_table=page_table)
    x = x + attn_out
    if enc is not None and "xattn" in p:
        hx = _apply_norm(cfg, p["ln_x"], x)
        x = x + A.cross_attn_forward(p["xattn"], cfg.attn_cfg(False), hx, enc)
    aux = jnp.float32(0.0)
    if "moe" in p:
        with jax.named_scope("moe"):
            h2 = _apply_norm(cfg, p["ln2"], x)
            out, aux = MOE.moe_forward(p["moe"], cfg.moe_cfg(), h2,
                                       n_groups=moe_groups)
    else:
        with jax.named_scope("ffn"):
            out = _ffn_apply(cfg, p["ffn"], _apply_norm(cfg, p["ln2"], x))
    return x + out, new_cache, aux


# ---------------------------------------------------------------------------
# Parameter construction
# ---------------------------------------------------------------------------
def build_params(cfg: ModelConfig, mk: Maker) -> Dict[str, Any]:
    p: Dict[str, Any] = {
        "embed": mk.table("embed", (), cfg.vocab, cfg.d_model),
        "ln_f": _norm_params(mk, cfg, "ln_f", ()),
    }
    if not cfg.tie_embeddings:
        p["lm_head"] = mk.dense("lm_head", (), cfg.d_model, cfg.vocab, scheme=None)

    L = cfg.n_layers
    if cfg.family in ("dense", "moe", "vlm"):
        p["layers"] = _tf_block_params(mk, cfg, (L,))
    elif cfg.family == "ssm":              # xLSTM: groups of (k-1) mLSTM + 1 sLSTM
        per = cfg.slstm_every
        assert L % per == 0, (L, per)
        g = L // per
        p["mlstm"] = S.mlstm_params(mk, cfg.mlstm_cfg(), (g, per - 1))
        p["mlstm_ln"] = _norm_params(mk, cfg, "ln1", (g, per - 1))
        p["slstm"] = S.slstm_params(mk, cfg.slstm_cfg(), (g,))
        p["slstm_ln"] = _norm_params(mk, cfg, "ln1", (g,))
        if cfg.d_ff:   # xlstm-350m has d_ff=0: FFN is folded into the blocks
            p["ffn"] = _ffn_params(mk, cfg, (g, per))
            p["ffn_ln"] = _norm_params(mk, cfg, "ln2", (g, per))
    elif cfg.family == "hybrid":           # Zamba2: shared attn every k mamba
        per = cfg.attn_every
        g, rem = divmod(L, per)
        p["mamba"] = S.mamba2_params(mk, cfg.mamba_cfg(), (L,))
        p["mamba_ln"] = _norm_params(mk, cfg, "ln1", (L,))
        p["shared_attn"] = _tf_block_params(mk, cfg, ())   # ONE shared block
    elif cfg.family == "audio":            # whisper enc-dec
        Le, Ld = cfg.encoder_layers, L - cfg.encoder_layers
        p["enc_layers"] = _tf_block_params(mk, cfg, (Le,), causal=False)
        p["enc_pos"] = mk.table("enc_pos", (), cfg.n_frames, cfg.d_model)
        p["enc_ln_f"] = _norm_params(mk, cfg, "enc_ln_f", ())
        p["dec_layers"] = _tf_block_params(mk, cfg, (Ld,), cross=True)
        p["dec_pos"] = mk.table("dec_pos", (), 32768, cfg.d_model)
    else:
        raise ValueError(cfg.family)
    return p


def _maybe_remat(fn, cfg: ModelConfig, mode: str):
    if cfg.remat and mode == "train":
        return jax.checkpoint(fn, policy=jax.checkpoint_policies.nothing_saveable)
    return fn


# ---------------------------------------------------------------------------
# Forward passes
# ---------------------------------------------------------------------------
def _embed(cfg: ModelConfig, params, tokens):
    return params["embed"][tokens].astype(jnp.bfloat16)


@jax.named_scope("lm_head")
def _logits(cfg: ModelConfig, params, x):
    x = _apply_norm(cfg, params["ln_f"], x)
    if cfg.tie_embeddings:
        w = params["embed"].astype(jnp.bfloat16)
        logits = jnp.einsum("bsd,vd->bsv", x, w, preferred_element_type=jnp.float32)
    else:
        logits = apply_linear(params["lm_head"], x, out_dtype=jnp.float32)
    if cfg.logit_softcap:
        c = cfg.logit_softcap
        logits = c * jnp.tanh(logits / c)
    return shard_act(logits, "logits")


def _scan_stack(cfg, mode, body, x0, layer_params, cache):
    """Scan ``body`` over a stacked layer dim; cache threaded as xs/ys."""
    def constrained(carry, xs):
        x, aux = carry
        return body((shard_act(x, "btd"), aux), xs)

    fn = _maybe_remat(constrained, cfg, mode)
    (x, aux), new_cache = jax.lax.scan(fn, (shard_act(x0, "btd"),
                                            jnp.float32(0.0)),
                                       (layer_params, cache))
    return x, aux, new_cache


def forward(cfg: ModelConfig, params, batch: Dict[str, Any], *,
            cache: Optional[Dict] = None, cache_index=None, mode: str = "train",
            page_table=None):
    """Unified forward.  mode: train | prefill | prefill_chunk | decode.

    batch: tokens [B, S]; vlm adds patches [B, Np, D]; audio adds frames
    [B, Sf, D].  Returns (logits [B, S(+Np), V], aux_loss, new_cache).

    ``prefill_chunk`` is the continuous-batching prefill step (DESIGN.md §7):
    like prefill it writes S new positions into the cache at ``cache_index``,
    but it attends over the *cache* (earlier chunks of the same prompt are
    already there) and returns logits for every chunk position, so the
    caller can read the true last-token logits out of a padded final chunk.
    ``decode`` additionally accepts a per-row [B] ``cache_index`` (each KV
    slot at its own length — the serving scheduler's batch).

    ``page_table`` [B, pages_per_slot] (paged serving, DESIGN.md §15):
    ``cache`` leaves are page arenas [L, n_pages, page_size, ...] and each
    layer's slab is gathered/scattered through the table inside the block
    (the table is a loop-invariant capture of the layer scan — pool
    families only).
    """
    assert mode in ("train", "prefill", "prefill_chunk", "decode"), mode
    assert page_table is None or cfg.family in ("dense", "moe", "vlm"), \
        "page_table is a slot-pool-family path (dense/moe/vlm)"
    tokens = batch["tokens"]
    x = _embed(cfg, params, tokens)
    positions = None

    if cfg.family == "vlm" and mode != "decode":
        patches = batch["patches"].astype(jnp.bfloat16)   # stub frontend output
        x = jnp.concatenate([patches, x], axis=1)
    if cfg.family == "vlm" and mode == "decode":
        # positions continue after the patch prefix (already in the cache)
        pass

    if cfg.family == "audio":
        return _forward_audio(cfg, params, batch, x, cache, cache_index, mode)

    # decode has 1 token per row: route every row as its OWN single-token
    # group — drop-free (capacity 1 covers each token's k distinct experts)
    # and row-independent, so one slot's tokens cannot depend on what else
    # shares the decode batch (continuous batching admits strangers and
    # rides garbage rows along in free slots; grouped routing would let
    # them steal expert capacity from real requests)
    moe_groups = None
    # prefill-from-empty: attend over local k/v (identical math; keeps the
    # KV-chunk scan off the sharded cache sequence axis)
    attend_local = mode == "prefill"

    if cfg.family in ("dense", "moe", "vlm"):
        def body(carry, xs):
            h, aux = carry
            lp, lcache = xs
            h, new_c, a = _tf_block_apply(cfg, lp, h, cache=lcache,
                                          cache_index=cache_index,
                                          moe_groups=moe_groups,
                                          attend_local=attend_local,
                                          page_table=page_table)
            return (h, aux + a), new_c
        x, aux, new_cache = _scan_stack(cfg, mode, body, x, params["layers"],
                                        cache)
        if mode == "prefill":   # serving needs only the last position's logits
            x = x[:, -1:]
        return _logits(cfg, params, x), aux / cfg.n_layers, new_cache

    if cfg.family == "ssm":
        return _forward_xlstm(cfg, params, x, cache, mode)
    if cfg.family == "hybrid":
        return _forward_zamba(cfg, params, x, cache, cache_index, mode,
                              attend_local)
    raise ValueError(cfg.family)


# --- xLSTM ------------------------------------------------------------------
def _forward_xlstm(cfg, params, x, cache, mode):
    g = cfg.n_layers // cfg.slstm_every
    per = cfg.slstm_every
    mcfg, scfg = cfg.mlstm_cfg(), cfg.slstm_cfg()

    def group(carry, xs):
        h, aux = carry
        h = shard_act(h, "btd")
        gp, gcache = xs

        def mblock(carry2, xs2):
            h2 = carry2
            lp, ln, lc = xs2
            state, conv = (lc["state"], lc["conv"]) if lc is not None else (None, None)
            out, (ns, ncv) = S.mlstm_forward(lp, mcfg, _apply_norm(cfg, ln, h2),
                                             state=state, conv_state=conv)
            h2 = h2 + out
            return h2, {"state": ns, "conv": ncv}

        m_cache = gcache["mlstm"] if gcache is not None else None
        h, new_m = jax.lax.scan(mblock, h,
                                (gp["mlstm"], gp["mlstm_ln"], m_cache))
        s_state = gcache["slstm"] if gcache is not None else None
        out, new_s = S.slstm_forward(gp["slstm"], scfg,
                                     _apply_norm(cfg, gp["slstm_ln"], h),
                                     state=s_state)
        h = h + out

        if cfg.d_ff:
            def fblock(carry2, xs2):
                h2 = carry2
                fp, fln = xs2
                return h2 + _ffn_apply(cfg, fp, _apply_norm(cfg, fln, h2)), None

            h, _ = jax.lax.scan(fblock, h, (gp["ffn"], gp["ffn_ln"]))
        new_cache = {"mlstm": new_m, "slstm": new_s}
        return (h, aux), new_cache

    gp = {"mlstm": params["mlstm"], "mlstm_ln": params["mlstm_ln"],
          "slstm": params["slstm"], "slstm_ln": params["slstm_ln"]}
    if cfg.d_ff:
        gp.update({"ffn": params["ffn"], "ffn_ln": params["ffn_ln"]})
    fn = _maybe_remat(group, cfg, mode)
    (x, aux), new_cache = jax.lax.scan(fn, (x, jnp.float32(0.0)), (gp, cache))
    if mode == "prefill":
        x = x[:, -1:]
    return _logits(cfg, params, x), aux, new_cache


# --- Zamba2 hybrid ------------------------------------------------------------
def _forward_zamba(cfg, params, x, cache, cache_index, mode,
                   attend_local=False):
    L, per = cfg.n_layers, cfg.attn_every
    g, rem = divmod(L, per)
    mcfg = cfg.mamba_cfg()

    def take(tree, sl, reshape=None):
        def f(a):
            v = a[sl]
            return v.reshape(reshape + v.shape[1:]) if reshape else v
        return jax.tree_util.tree_map(f, tree)

    mamba_main = take({"p": params["mamba"], "ln": params["mamba_ln"]},
                      slice(0, g * per), (g, per))
    mamba_tail = take({"p": params["mamba"], "ln": params["mamba_ln"]},
                      slice(g * per, L))

    def mblock(carry, xs):
        h = carry
        lp, lc = xs
        state, conv = (lc["state"], lc["conv"]) if lc is not None else (None, None)
        out, (ns, ncv) = S.mamba2_forward(lp["p"], mcfg,
                                          _apply_norm(cfg, lp["ln"], h),
                                          state=state, conv_state=conv)
        return h + out, {"state": ns, "conv": ncv}

    inner_block = _maybe_remat(mblock, cfg, mode)

    def group(carry, xs):
        h, aux = carry
        h = shard_act(h, "btd")
        gp, gcache = xs
        m_cache = gcache["mamba"] if gcache is not None else None
        h, new_m = jax.lax.scan(inner_block, h, (gp, m_cache))
        a_cache = gcache["attn"] if gcache is not None else None
        h, new_a, a_aux = _tf_block_apply(cfg, params["shared_attn"], h,
                                          cache=a_cache, cache_index=cache_index,
                                          attend_local=attend_local)
        new_cache = {"mamba": new_m, "attn": new_a}
        return (h, aux + a_aux), new_cache

    main_cache = cache["groups"] if cache is not None else None
    fn = _maybe_remat(group, cfg, mode)
    (x, aux), new_groups = jax.lax.scan(fn, (x, jnp.float32(0.0)),
                                        (mamba_main, main_cache))
    tail_cache = cache["tail"] if cache is not None else None
    x, new_tail = jax.lax.scan(inner_block, x, (mamba_tail, tail_cache))
    new_cache = {"groups": new_groups, "tail": new_tail}
    if mode == "prefill":
        x = x[:, -1:]
    return _logits(cfg, params, x), aux, new_cache


# --- Whisper (audio enc-dec) --------------------------------------------------
def _forward_audio(cfg, params, batch, x_dec, cache, cache_index, mode):
    Le = cfg.encoder_layers

    if mode in ("train", "prefill") or cache is None:
        frames = batch["frames"].astype(jnp.bfloat16)      # stub frontend
        enc = frames + params["enc_pos"][None, : frames.shape[1]].astype(jnp.bfloat16)

        def eblock(carry, lp):
            h, aux = carry
            h, _, a = _tf_block_apply(cfg, lp, shard_act(h, "btd"),
                                      causal=False)
            return (h, aux + a), None
        fn = _maybe_remat(eblock, cfg, mode)
        (enc, aux_e), _ = jax.lax.scan(fn, (enc, jnp.float32(0.0)),
                                       params["enc_layers"])
        enc = _apply_norm(cfg, params["enc_ln_f"], enc)
    else:
        enc = cache["enc"]
        aux_e = jnp.float32(0.0)

    base = 0 if cache_index is None else cache_index
    s = x_dec.shape[1]
    pos = jax.lax.dynamic_slice_in_dim(params["dec_pos"], base, s, axis=0) \
        if mode == "decode" else params["dec_pos"][:s]
    x = x_dec + pos[None].astype(jnp.bfloat16)

    def dblock(carry, xs):
        h, aux = carry
        lp, lcache = xs
        h, new_c, a = _tf_block_apply(cfg, lp, shard_act(h, "btd"),
                                      cache=lcache,
                                      cache_index=cache_index, enc=enc,
                                      attend_local=(mode == "prefill"))
        return (h, aux + a), new_c

    dec_cache = cache["dec"] if cache is not None else None
    fn = _maybe_remat(dblock, cfg, mode)
    (x, aux_d), new_dec = jax.lax.scan(fn, (x, jnp.float32(0.0)),
                                       (params["dec_layers"], dec_cache))
    new_cache = None if cache is None else {"enc": enc, "dec": new_dec}
    if mode == "prefill":
        x = x[:, -1:]
    return _logits(cfg, params, x), aux_e + aux_d, new_cache


# ---------------------------------------------------------------------------
# Cache construction
# ---------------------------------------------------------------------------
def init_cache(cfg: ModelConfig, batch: int, max_len: int, *,
               abstract: bool = False, kv_dtype=jnp.bfloat16):
    """Stacked per-layer cache tree (zeros, or ShapeDtypeStructs).

    ``kv_dtype``: storage of the attention KV slabs — a jnp dtype / 'bf16'
    for plain slabs, or a KV quantization scheme name ('int8' / 'fp8'), in
    which case each slab is a ``QuantizedKV`` pytree node of packed codes +
    per-(position, head) scales (DESIGN.md §9).  Recurrent state (ssm /
    mamba) and the audio encoder output always stay in their native dtypes.
    """
    def kv(stack, b=batch, s=max_len):
        if cfg.use_mla:
            spec = A.mla_cache_spec(cfg.mla_cfg(), b, s, kv_dtype)
        else:
            spec = A.gqa_cache_spec(cfg.attn_cfg(), b, s, kv_dtype)
        return jax.tree_util.tree_map(
            lambda sd: jax.ShapeDtypeStruct(stack + sd.shape, sd.dtype), spec)

    def mamba_c(stack):
        sts, conv = S.mamba2_state_spec(cfg.mamba_cfg(), batch)
        f = lambda sd: jax.ShapeDtypeStruct(stack + sd.shape, sd.dtype)
        return {"state": jax.tree_util.tree_map(f, sts),
                "conv": jax.tree_util.tree_map(f, conv)}

    def mlstm_c(stack):
        sts, conv = S.mlstm_state_spec(cfg.mlstm_cfg(), batch)
        f = lambda sd: jax.ShapeDtypeStruct(stack + sd.shape, sd.dtype)
        return {"state": jax.tree_util.tree_map(f, sts),
                "conv": jax.tree_util.tree_map(f, conv)}

    if cfg.family in ("dense", "moe", "vlm"):
        spec = kv((cfg.n_layers,))
    elif cfg.family == "ssm":
        g, per = cfg.n_layers // cfg.slstm_every, cfg.slstm_every
        f = lambda sd: jax.ShapeDtypeStruct((g,) + sd.shape, sd.dtype)
        spec = {"mlstm": jax.tree_util.tree_map(
                    lambda sd: jax.ShapeDtypeStruct((g, per - 1) + sd.shape, sd.dtype),
                    mlstm_c(())),
                "slstm": jax.tree_util.tree_map(
                    f, S.slstm_state_spec(cfg.slstm_cfg(), batch))}
    elif cfg.family == "hybrid":
        g, rem = divmod(cfg.n_layers, cfg.attn_every)
        spec = {"groups": {"mamba": jax.tree_util.tree_map(
                               lambda sd: jax.ShapeDtypeStruct(
                                   (g, cfg.attn_every) + sd.shape, sd.dtype),
                               mamba_c(())),
                           "attn": kv((g,))},
                "tail": jax.tree_util.tree_map(
                    lambda sd: jax.ShapeDtypeStruct((rem,) + sd.shape, sd.dtype),
                    mamba_c(()))}
    elif cfg.family == "audio":
        Ld = cfg.n_layers - cfg.encoder_layers
        spec = {"enc": jax.ShapeDtypeStruct((batch, cfg.n_frames, cfg.d_model),
                                            jnp.bfloat16),
                "dec": kv((Ld,))}
    else:
        raise ValueError(cfg.family)

    if abstract:
        return spec
    return jax.tree_util.tree_map(lambda sd: jnp.zeros(sd.shape, sd.dtype), spec)


# ---------------------------------------------------------------------------
# Loss / train step
# ---------------------------------------------------------------------------
def loss_fn(cfg: ModelConfig, params, batch, *, aux_weight: float = 0.01,
            z_weight: float = 1e-4):
    logits, aux, _ = forward(cfg, params, batch, mode="train")
    labels = batch["labels"]
    if cfg.family == "vlm":       # logits cover [patches + tokens]
        logits = logits[:, cfg.n_patches:]
    lf = logits.astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(lf, axis=-1)
    gold = jnp.take_along_axis(lf, labels[..., None].clip(0), axis=-1)[..., 0]
    mask = (labels >= 0).astype(jnp.float32)
    denom = jnp.maximum(mask.sum(), 1.0)
    xent = jnp.sum((lse - gold) * mask) / denom
    zloss = jnp.sum((lse ** 2) * mask) / denom
    loss = xent + aux_weight * aux + z_weight * zloss
    return loss, {"xent": xent, "aux": aux, "zloss": zloss}
