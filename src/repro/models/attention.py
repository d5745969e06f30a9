"""Attention layers: GQA (+RoPE), MLA (DeepSeek-V2), cross-attention.

Two execution modes, chosen by query length:
  * **Chunked online-softmax** (train / prefill): ``lax.scan`` over KV chunks
    with running (max, sum, acc) — flash-attention recurrence in pure jnp.
    Keeps peak memory at one [.., Sq, chunk] score block and keeps the HLO
    small for 512-device compiles.
  * **Dense split-KV** (decode, Sq == 1): one einsum over the full KV length
    so the KV sequence axis can be sharded (flash-decode style); GSPMD turns
    the softmax/contraction over the sharded axis into the partial-softmax +
    all-reduce combine pattern.  With the execution policy pinned to
    ``kernel='pallas'`` (``PrecisionPolicy`` / ``ops.declare_execution``)
    the GQA decode branch instead runs the fused Pallas flash-decode kernel
    (``kernels/decode_attention.py``): packed KV blocks stream out of the
    pool and dequantize in-kernel; the einsum path here is kept as the
    interpret-mode oracle (DESIGN.md §9).

Projection weights go through ``apply_linear`` and may be quantized
(the paper's technique applies to projection MACs); the attention MACs
themselves (QK^T, PV) stay BF16xBF16 — exactly the paper's Table I split.
The KV *cache* may additionally be stored quantized (``kv_dtype`` = 'int8'
/ 'fp8'): writes quantize per (position, head) group inside the jitted
steps, reads dequantize (einsum path) or stream packed codes (kernel path).

Shapes: x [B, S, D]; heads layout [B, S, H, Dh].
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.quant.kv_cache import (cache_read, cache_write_rows,
                                  cache_write_slice, gather_pages,
                                  kv_slab_pspec, kv_slab_spec, scatter_pages)
from repro.quant.schemes import get_kv_scheme

from .common import (Maker, apply_linear, apply_rope, rms_norm,
                     shard_act)

_NEG = -1e30  # -inf stand-in that keeps exp() NaN-free on fully-masked rows


@dataclasses.dataclass(frozen=True)
class AttnConfig:
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_head: int
    rope_theta: float = 10000.0
    use_rope: bool = True
    causal: bool = True
    qkv_scheme: Optional[str] = None    # quantization scheme for projections
    kv_chunk: int = 512
    use_bias: bool = False              # a bias on each of q, k, v and o


# ---------------------------------------------------------------------------
# Parameter construction (Maker-driven; see common.py)
# ---------------------------------------------------------------------------
def attn_params(mk: Maker, cfg: AttnConfig, stack: Tuple[int, ...]) -> Dict[str, Any]:
    d, h, hk, dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    s = cfg.qkv_scheme
    p = {
        "wq": mk.dense("attn.wq", stack, d, h * dh, scheme=s),
        "wk": mk.dense("attn.wk", stack, d, hk * dh, scheme=s),
        "wv": mk.dense("attn.wv", stack, d, hk * dh, scheme=s),
        "wo": mk.dense("attn.wo", stack, h * dh, d, scheme=s),
    }
    if cfg.use_bias:
        for w, n in (("q", h * dh), ("k", hk * dh), ("v", hk * dh),
                     ("o", d)):
            p["b" + w] = mk.bias(f"attn.w{w}.b", stack, n)
    return p


def cross_attn_params(mk: Maker, cfg: AttnConfig, stack) -> Dict[str, Any]:
    return attn_params(mk, cfg, stack)


def _proj(params, w: str, x):
    """x @ projection ``w`` ('q', 'k', 'v', 'o'), plus its bias if it has
    one."""
    return apply_linear(params["w" + w], x, bias=params.get("b" + w))


# ---------------------------------------------------------------------------
# Core softmax attention (both execution modes)
# ---------------------------------------------------------------------------
def _repeat_kv(x, rep: int):
    if rep == 1:
        return x
    b, s, hk, dh = x.shape
    return jnp.broadcast_to(x[:, :, :, None, :], (b, s, hk, rep, dh)).reshape(
        b, s, hk * rep, dh
    )


def attend(q, k, v, *, causal: bool, q_offset=0, kv_chunk: int = 512,
           kv_valid_len=None):
    """Softmax attention.  q [B,Sq,H,Dh]; k,v [B,Sk,Hk,Dh] -> [B,Sq,H,Dh].

    ``q_offset``: absolute position of q[0] (decode: cache length).
    ``kv_valid_len``: optional [B] count of valid KV positions (ragged cache).
    """
    b, sq, h, dh = q.shape
    sk, hk = k.shape[1], k.shape[2]
    scale = 1.0 / jnp.sqrt(jnp.float32(dh))
    if sq == 1 or sk <= kv_chunk or sk % kv_chunk != 0:
        return _attend_dense(q, k, v, causal=causal, q_offset=q_offset,
                             kv_valid_len=kv_valid_len, scale=scale)
    return _attend_chunked(q, k, v, causal=causal, q_offset=q_offset,
                           kv_chunk=kv_chunk, kv_valid_len=kv_valid_len,
                           scale=scale)


def _mask_bias(causal, q_offset, sq, sk, k_offset, kv_valid_len, b):
    """[B or 1, Sq, Sk_chunk] additive f32 bias (0 or _NEG).

    ``q_offset`` may be a scalar (whole batch at one position — the static
    one-shot path) or a [B] vector (continuous batching: every cache slot
    sits at its own length).
    """
    q_off = jnp.reshape(jnp.asarray(q_offset), (-1, 1, 1))   # [B or 1, 1, 1]
    qpos = q_off + jnp.arange(sq)[None, :, None]             # [B or 1, Sq, 1]
    kpos = k_offset + jnp.arange(sk)[None, None, :]          # [1, 1, Sk]
    ok = jnp.broadcast_to(kpos <= qpos if causal else
                          jnp.ones((1, 1, sk), bool),
                          (qpos.shape[0], sq, sk))
    bias = jnp.where(ok, 0.0, _NEG)                          # [B or 1, Sq, Sk]
    if kv_valid_len is not None:
        valid = kpos < kv_valid_len[:, None, None]           # [B, 1, Sk]
        bias = jnp.where(valid, bias, _NEG)
    return bias


def _attend_dense(q, k, v, *, causal, q_offset, kv_valid_len, scale):
    """Grouped-GQA attention: K/V are NEVER materialized per query head —
    the einsums carry an explicit (group, rep) split; inputs stay bf16 with
    f32 accumulation (preferred_element_type), so no f32 copy of the KV
    cache is created either (decisive for 32k-cache decode).  Scores are
    kept in FLAT-head layout [b, h, sq, sk] so the full 16-way 'model' axis
    shards them (the grouped dims hk < 16 could not)."""
    b, sq, h, dh = q.shape
    sk, hk = k.shape[1], k.shape[2]
    dv = v.shape[-1]
    rep = h // hk
    qg = (q * scale.astype(q.dtype)).reshape(b, sq, hk, rep, dh)
    # bf16-storage dots: on TPU the MXU accumulates in f32 natively; asking
    # for an f32 result here makes the CPU backend hoist an f32 COPY of the
    # whole KV cache into the decode loop carry (verified in the dry-run
    # HLO), so the f32 upcast happens after the contraction instead.
    s = jnp.einsum("bqhrd,bkhd->bhrqk", qg, k).astype(jnp.float32)
    s = shard_act(s.reshape(b, h, sq, sk), "bhqk")
    bias = _mask_bias(causal, q_offset, sq, sk, 0, kv_valid_len, b)
    s = s + bias[:, None]
    p = jax.nn.softmax(s, axis=-1)
    pg = p.reshape(b, hk, rep, sq, sk).astype(v.dtype)
    out = jnp.einsum("bhrqk,bkhd->bqhrd", pg, v)
    return out.reshape(b, sq, h, dv).astype(q.dtype)


def _attend_chunked(q, k, v, *, causal, q_offset, kv_chunk, kv_valid_len, scale):
    b, sq, h, dh = q.shape
    sk, hk = k.shape[1], k.shape[2]
    dv = v.shape[-1]
    rep = h // hk
    assert sk % kv_chunk == 0, (sk, kv_chunk)
    n_chunks = sk // kv_chunk
    qg = (q * scale.astype(q.dtype)).reshape(b, sq, hk, rep, dh)

    kc = jnp.moveaxis(k.reshape(b, n_chunks, kv_chunk, hk, dh), 1, 0)
    vc = jnp.moveaxis(v.reshape(b, n_chunks, kv_chunk, hk, dv), 1, 0)

    def body(carry, inp):
        m, l, acc = carry                      # flat-h: [b,h,sq], [...,dv]
        kci, vci, idx = inp
        # bf16-storage dots (see _attend_dense) — accumulation across
        # chunks stays f32 in the carry
        s = jnp.einsum("bqhrd,bkhd->bhrqk", qg, kci).astype(jnp.float32)
        s = shard_act(s.reshape(b, h, sq, kv_chunk), "bhqk")
        bias = _mask_bias(causal, q_offset, sq, kv_chunk, idx * kv_chunk,
                          kv_valid_len, b)
        s = s + bias[:, None]
        m_new = jnp.maximum(m, s.max(-1))
        corr = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[..., None])
        l_new = l * corr + p.sum(-1)
        pg = p.reshape(b, hk, rep, sq, kv_chunk).astype(vci.dtype)
        pv = jnp.einsum("bhrqk,bkhd->bhrqd", pg, vci).astype(jnp.float32)
        pv = shard_act(pv.reshape(b, h, sq, dv), "bhqd")
        acc_new = acc * corr[..., None] + pv
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((b, h, sq), _NEG, jnp.float32)
    l0 = jnp.zeros((b, h, sq), jnp.float32)
    a0 = shard_act(jnp.zeros((b, h, sq, dv), jnp.float32), "bhqd")
    (m, l, acc), _ = jax.lax.scan(
        body, (m0, l0, a0), (kc, vc, jnp.arange(n_chunks))
    )
    out = acc / jnp.maximum(l, 1e-30)[..., None]      # [b,h,sq,dv]
    out = jnp.moveaxis(out, 2, 1)                      # [b,sq,h,dv]
    return out.reshape(b, sq, h, dv).astype(q.dtype)


# ---------------------------------------------------------------------------
# GQA self-attention layer (with optional KV cache for serving)
# ---------------------------------------------------------------------------
def gqa_forward(params, cfg: AttnConfig, x, *, positions=None,
                cache: Optional[Tuple] = None, cache_index=None,
                attend_local: bool = False, page_table=None):
    """x [B, S, D] -> (out [B, S, D], new_cache).

    cache = (k_cache [B, Smax, Hk, Dh], v_cache ...) with ``cache_index`` the
    write offset (prefill: 0; decode: current length).  ``cache_index`` may
    be a [B] vector (decode only, s == 1): row i writes at its own slot
    length — the continuous-batching path where every sequence in the batch
    is at a different position.  No cache: plain causal self-attention over
    x itself.  ``attend_local``: write the cache but attend over the
    freshly-computed k/v (prefill-from-empty: identical math, and keeps the
    chunked scan off the sharded cache sequence axis).

    ``page_table`` (paged serving, DESIGN.md §15): when given, ``cache`` is
    a page *arena* [n_pages, page_size, Hk, Dh] per slab and ``page_table``
    [B, pages_per_slot] maps each batch row to its pages.  The arena is
    gathered into the per-row virtual slab up front, ALL write/attend logic
    below runs unchanged on that slab (identical bytes, identical shapes —
    the bit-identity contract with the slab pool), and the updated slab is
    scattered back through the table on the way out.
    """
    b, s, d = x.shape
    h, hk, dh = cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    arena = None
    if cache is not None and page_table is not None:
        arena = cache
        with jax.named_scope("attn_core"):
            cache = tuple(gather_pages(a, page_table) for a in arena)
    q = shard_act(_proj(params, "q", x).reshape(b, s, h, dh), "bthd")
    k = shard_act(_proj(params, "k", x).reshape(b, s, hk, dh), "bthd")
    v = shard_act(_proj(params, "v", x).reshape(b, s, hk, dh), "bthd")

    per_row = cache_index is not None and jnp.ndim(cache_index) == 1
    if positions is None:
        base = jnp.asarray(0 if cache_index is None else cache_index)
        positions = (base[:, None] if per_row else base) \
            + jnp.arange(s)[None, :]                         # [B or 1, S]
    if cfg.use_rope:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)

    # cache write and read, scores, softmax, PV: up to the output projection
    with jax.named_scope("attn_core"):
        new_cache = None
        if cache is not None:
            k_cache, v_cache = cache
            if per_row:
                assert s == 1, "per-row cache_index is a decode-only path"
                rows = jnp.arange(b)
                k_cache = cache_write_rows(k_cache, k, rows, cache_index)
                v_cache = cache_write_rows(v_cache, v, rows, cache_index)
            else:
                k_cache = cache_write_slice(k_cache, k, cache_index)
                v_cache = cache_write_slice(v_cache, v, cache_index)
            new_cache = (k_cache, v_cache)

        if cache is None or attend_local:
            out = attend(q, k, v, causal=cfg.causal, q_offset=0,
                         kv_chunk=cfg.kv_chunk)
        else:
            k_cache, v_cache = new_cache
            valid = jnp.broadcast_to(
                jnp.asarray(cache_index + s, jnp.int32), (b,))
            out = None
            if s == 1 and cfg.causal:
                # fused flash-decode when the execution policy selects it:
                # streams (packed) KV blocks straight from the pool slab,
                # dequantizes in-kernel, no [B,S,H,D] copy — shard_map'd over
                # a declared mesh (slots on 'data', KV heads on 'model')
                from repro.kernels.ops import fused_decode_attention
                out = fused_decode_attention(q, k_cache, v_cache, valid)
            if out is None:
                out = attend(q, cache_read(k_cache), cache_read(v_cache),
                             causal=cfg.causal, q_offset=cache_index,
                             kv_chunk=cfg.kv_chunk, kv_valid_len=valid)

        if arena is not None and new_cache is not None:
            new_cache = tuple(scatter_pages(a, page_table, v)
                              for a, v in zip(arena, new_cache))
    out = out.reshape(b, s, h * dh)
    return _proj(params, "o", out), new_cache


def gqa_cache_spec(cfg: AttnConfig, batch: int, max_len: int, dtype=jnp.bfloat16):
    """``dtype`` is the pool knob: a jnp dtype / 'bf16' for plain slabs, or
    a KV scheme name ('int8'/'fp8') for packed-codes + scales slabs."""
    shape = (batch, max_len, cfg.n_kv_heads, cfg.d_head)
    return (kv_slab_spec(shape, dtype), kv_slab_spec(shape, dtype))


def gqa_cache_pspec(cfg: AttnConfig, kv_dtype, slot_ax, head_ax):
    """PartitionSpec twin of ``gqa_cache_spec`` for one pool layer
    [slots, S, H, D]: slots on ``slot_ax`` (DP), heads on ``head_ax`` (TP),
    sequence and d_head local (per-slot writes land at traced offsets;
    packed codes cannot split along d_head)."""
    s = kv_slab_pspec((slot_ax, None, head_ax, None), kv_dtype)
    return (s, s)


# ---------------------------------------------------------------------------
# Cross-attention (whisper decoder): KV from encoder output, no mask, no rope
# ---------------------------------------------------------------------------
def cross_attn_forward(params, cfg: AttnConfig, x, enc):
    """x [B, Sq, D] attends over enc [B, Sk, D]."""
    b, sq, d = x.shape
    sk = enc.shape[1]
    h, hk, dh = cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    q = shard_act(_proj(params, "q", x).reshape(b, sq, h, dh), "bthd")
    k = shard_act(_proj(params, "k", enc).reshape(b, sk, hk, dh), "bthd")
    v = shard_act(_proj(params, "v", enc).reshape(b, sk, hk, dh), "bthd")
    out = attend(q, k, v, causal=False, kv_chunk=cfg.kv_chunk)
    return _proj(params, "o", out.reshape(b, sq, h * dh))


# ---------------------------------------------------------------------------
# MLA — Multi-head Latent Attention (DeepSeek-V2)
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class MLAConfig:
    d_model: int
    n_heads: int
    q_lora: int          # query low-rank dim (0 = dense q projection)
    kv_lora: int         # compressed KV latent dim (the cached quantity)
    d_head_nope: int     # per-head non-rope dim
    d_head_rope: int     # shared rope dim
    d_head_v: int        # per-head value dim
    rope_theta: float = 10000.0
    qkv_scheme: Optional[str] = None
    kv_chunk: int = 512


def mla_params(mk: Maker, cfg: MLAConfig, stack) -> Dict[str, Any]:
    d, h = cfg.d_model, cfg.n_heads
    s = cfg.qkv_scheme
    p: Dict[str, Any] = {
        # KV compression: latent + shared rope key
        "w_dkv": mk.dense("attn.w_dkv", stack, d, cfg.kv_lora + cfg.d_head_rope, scheme=s),
        "kv_norm": mk.norm("attn.kv_norm", stack, cfg.kv_lora),
        # per-head expansions out of the latent
        "w_uk": mk.dense("attn.w_uk", stack, cfg.kv_lora, h * cfg.d_head_nope, scheme=s),
        "w_uv": mk.dense("attn.w_uv", stack, cfg.kv_lora, h * cfg.d_head_v, scheme=s),
        "wo": mk.dense("attn.wo", stack, h * cfg.d_head_v, d, scheme=s),
    }
    if cfg.q_lora:
        p["w_dq"] = mk.dense("attn.w_dq", stack, d, cfg.q_lora, scheme=s)
        p["q_norm"] = mk.norm("attn.q_norm", stack, cfg.q_lora)
        p["w_uq"] = mk.dense("attn.w_uq", stack, cfg.q_lora,
                             h * (cfg.d_head_nope + cfg.d_head_rope), scheme=s)
    else:
        p["w_uq"] = mk.dense("attn.w_uq", stack, d,
                             h * (cfg.d_head_nope + cfg.d_head_rope), scheme=s)
    return p


def _mla_queries(params, cfg: MLAConfig, x, positions):
    b, s, _ = x.shape
    h = cfg.n_heads
    if cfg.q_lora:
        cq = rms_norm(apply_linear(params["w_dq"], x), params["q_norm"])
        q = apply_linear(params["w_uq"], cq)
    else:
        q = apply_linear(params["w_uq"], x)
    q = shard_act(q.reshape(b, s, h, cfg.d_head_nope + cfg.d_head_rope),
                  "bthd")
    q_nope, q_rope = jnp.split(q, [cfg.d_head_nope], axis=-1)
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta)
    return q_nope, q_rope


def mla_forward(params, cfg: MLAConfig, x, *, cache=None, cache_index=None,
                positions=None, attend_local: bool = False, page_table=None):
    """MLA attention.  cache = (c_kv [B,Smax,kv_lora], k_rope [B,Smax,Dr]).

    Prefill/train path expands K/V per position; the decode path (Sq==1)
    uses the *absorbed* formulation — scores and values computed directly in
    the compressed latent space (the MLA serving trick), so cached bytes are
    kv_lora + d_head_rope per token regardless of head count.  As in
    ``gqa_forward``, ``cache_index`` may be a [B] vector for per-slot decode.
    ``page_table`` gathers/scatters the latent + rope arenas exactly as in
    ``gqa_forward`` — the offsets differ (no head axis) but the pages are
    the same [page, position, ...] layout (DESIGN.md §15).
    """
    b, s, d = x.shape
    h = cfg.n_heads
    arena = None
    if cache is not None and page_table is not None:
        arena = cache
        with jax.named_scope("attn_core"):
            cache = tuple(gather_pages(a, page_table) for a in arena)
    per_row = cache_index is not None and jnp.ndim(cache_index) == 1
    if positions is None:
        base = jnp.asarray(0 if cache_index is None else cache_index)
        positions = (base[:, None] if per_row else base) \
            + jnp.arange(s)[None, :]
    q_nope, q_rope = _mla_queries(params, cfg, x, positions)

    ckr = apply_linear(params["w_dkv"], x)
    c_kv, k_rope = jnp.split(ckr, [cfg.kv_lora], axis=-1)
    c_kv = rms_norm(c_kv, params["kv_norm"])
    k_rope = apply_rope(k_rope[:, :, None, :], positions, cfg.rope_theta)[:, :, 0]

    with jax.named_scope("attn_core"):
        new_cache = None
        valid = None
        q_off = 0
        if cache is not None:
            c_cache, r_cache = cache
            if per_row:
                assert s == 1, "per-row cache_index is a decode-only path"
                rows = jnp.arange(b)
                new_cache = (
                    cache_write_rows(c_cache, c_kv, rows, cache_index),
                    cache_write_rows(r_cache, k_rope, rows, cache_index))
            else:
                new_cache = (cache_write_slice(c_cache, c_kv, cache_index),
                             cache_write_slice(r_cache, k_rope, cache_index))
            if not attend_local:   # attend over the cache (decode / chunks)
                c_kv, k_rope = new_cache
                valid = jnp.broadcast_to(
                    jnp.asarray(cache_index + s, jnp.int32), (b,))
                q_off = cache_index

        if s == 1 and cache is not None:
            out = _mla_decode_absorbed(params, cfg, q_nope, q_rope, c_kv,
                                       k_rope, valid, q_off)
        else:
            out = _mla_expanded(params, cfg, q_nope, q_rope, c_kv, k_rope,
                                valid, q_off, s)
        if arena is not None and new_cache is not None:
            new_cache = tuple(scatter_pages(a, page_table, v)
                              for a, v in zip(arena, new_cache))
    return apply_linear(params["wo"], out.reshape(b, s, h * cfg.d_head_v)), new_cache


def _mla_expanded(params, cfg, q_nope, q_rope, c_kv, k_rope, valid, q_off, sq):
    b, sk = c_kv.shape[0], c_kv.shape[1]
    h = cfg.n_heads
    k_nope = shard_act(apply_linear(params["w_uk"], c_kv)
                       .reshape(b, sk, h, cfg.d_head_nope), "bthd")
    v = shard_act(apply_linear(params["w_uv"], c_kv)
                  .reshape(b, sk, h, cfg.d_head_v), "bthd")
    # fold the shared rope key in as extra head dims (standard MLA trick)
    k_full = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope[:, :, None, :], (b, sk, h, cfg.d_head_rope))],
        axis=-1)
    q_full = jnp.concatenate([q_nope, q_rope], axis=-1)
    return attend(q_full, k_full, v, causal=True, q_offset=q_off,
                  kv_chunk=cfg.kv_chunk, kv_valid_len=valid)


def _mla_decode_absorbed(params, cfg, q_nope, q_rope, c_kv, k_rope, valid, q_off):
    """Absorbed decode: scores/values in latent space; never expand K/V."""
    b, sk = c_kv.shape[0], c_kv.shape[1]
    h = cfg.n_heads
    # absorb W_uk into the query:  q_lat [B,1,H,kv_lora]
    w_uk = params["w_uk"]
    from .common import QLinear
    if isinstance(w_uk, QLinear):  # dequantize for the absorbed contraction
        from repro.quant.schemes import QuantizedLinearWeights, get_scheme, dequantize
        w_uk_d = dequantize(QuantizedLinearWeights(
            get_scheme(w_uk.scheme_name), w_uk.packed, w_uk.scales, w_uk.shape),
            dtype=jnp.bfloat16)
    else:
        w_uk_d = w_uk
    w_uk_h = w_uk_d.reshape(cfg.kv_lora, h, cfg.d_head_nope)
    q_lat = jnp.einsum("bqhd,lhd->bqhl", q_nope, w_uk_h.astype(q_nope.dtype))
    scale = 1.0 / jnp.sqrt(jnp.float32(cfg.d_head_nope + cfg.d_head_rope))
    # latent cache stays bf16 in the einsums (no f32 copy of the 32k cache);
    # scores upcast to f32 AFTER the contraction (MXU accumulates f32
    # internally on TPU — bf16 here is the storage type of the result)
    s_lat = jnp.einsum("bqhl,bkl->bhqk", q_lat.astype(c_kv.dtype), c_kv)
    s_rope = jnp.einsum("bqhd,bkd->bhqk", q_rope, k_rope.astype(q_rope.dtype))
    s = (s_lat.astype(jnp.float32) + s_rope.astype(jnp.float32)) * scale
    kpos = jnp.arange(sk)[None, None, None, :]
    if valid is not None:
        s = jnp.where(kpos < valid[:, None, None, None], s, _NEG)
    p = jax.nn.softmax(s, axis=-1)
    o_lat = jnp.einsum("bhqk,bkl->bqhl", p.astype(c_kv.dtype), c_kv)
    # absorb W_uv on the way out
    w_uv = params["w_uv"]
    if isinstance(w_uv, QLinear):
        from repro.quant.schemes import QuantizedLinearWeights, get_scheme, dequantize
        w_uv_d = dequantize(QuantizedLinearWeights(
            get_scheme(w_uv.scheme_name), w_uv.packed, w_uv.scales, w_uv.shape),
            dtype=jnp.bfloat16)
    else:
        w_uv_d = w_uv
    w_uv_h = w_uv_d.reshape(cfg.kv_lora, h, cfg.d_head_v)
    out = jnp.einsum("bqhl,lhd->bqhd", o_lat, w_uv_h.astype(o_lat.dtype))
    return out.astype(q_nope.dtype)


def mla_cache_spec(cfg: MLAConfig, batch: int, max_len: int, dtype=jnp.bfloat16):
    if get_kv_scheme(dtype) is not None:
        raise ValueError(
            f"kv_dtype={dtype!r}: KV quantization covers the GQA per-head "
            "cache; the MLA latent cache is already compressed (kv_lora per "
            "token) and stays bf16 — see DESIGN.md §9")
    return (jax.ShapeDtypeStruct((batch, max_len, cfg.kv_lora), dtype),
            jax.ShapeDtypeStruct((batch, max_len, cfg.d_head_rope), dtype))


def mla_cache_pspec(cfg: MLAConfig, slot_ax):
    """PartitionSpec twin of ``mla_cache_spec`` for one pool layer: the
    compressed latent and shared rope key have no head axis — only the slot
    dim shards (the latent is consumed whole by every head's absorbed
    contraction, so splitting it would shard a contraction dim)."""
    from jax.sharding import PartitionSpec as P
    return (P(slot_ax, None, None), P(slot_ax, None, None))
