"""Pallas TPU flash-decode kernel: fused decode attention over the KV pool.

The serving hot path is ``Sq == 1`` GQA attention over a slot-pooled cache
(DESIGN.md §7).  The einsum path in ``models/attention.py`` dispatches a
score einsum, a softmax and a value einsum per layer per token — and, with
a quantized pool (DESIGN.md §9), additionally materializes a dequantized
[B, S, H, D] copy of the cache.  This kernel fuses the whole thing:

  grid (B, Sk/bk) — one program per (slot row, KV block)
  * stream one KV block of ALL the slot's KV heads — packed
    [bk, Hk, D/4] int32 + scales [bk, Hk] (or bf16 [bk, Hk, D]) — from the
    pool slab into VMEM,
  * per KV head: dequantize in-kernel — arithmetic shift/mask decode with
    DAZ + implicit-one restore (XtraMAC Stage-1 semantics; no gathers, the
    same decode ``packed_matmul`` uses for weights) — and run one split-KV
    online-softmax update (running max / normalizer / f32 accumulator), the
    flash-decode recurrence over the block grid axis,
  * final block normalizes and writes the [Hk, rep, D] output tile.

Block shapes follow the TPU tiling rule (the last two block dims divisible
by (8, 128) or equal to the array's): KV blocks span the whole
(Hk, D) tail, scales the whole Hk tail, and the per-slot valid lengths sit
in SMEM, read by program id.

Numerics are f32 end-to-end after the bf16 loads: strictly more accurate
than the einsum path (which rounds scores and probabilities through bf16
storage between dispatches).  The bit-exactness contract is therefore
against ``kernels/ref.py:decode_attention_ref`` — the same block updates
(shared ``_flash_update``) as a plain jnp loop — not against the einsum
path, which agrees to bf16 rounding tolerance (DESIGN.md §9).

The running (m, l) carries live in two small revisited output tiles rather
than scratch, matching ``packed_matmul``'s revisiting-accumulate pattern
(TPU grids iterate the last axis innermost, so all Sk blocks of one
slot row run consecutively).  Validated under interpret mode on CPU and
compiled for a v5e in ``tests/test_tpu_compile.py``.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret
from repro.quant.kv_cache import QuantizedKV
from repro.quant.schemes import kv_unpack_codes

from .packed_matmul import _decode_fp8_e4m3, _decode_int

_NEG = -1e30  # -inf stand-in; matches models/attention.py masking


# ---------------------------------------------------------------------------
# Shared block math — used verbatim by the kernel body AND the jnp oracle in
# ref.py, which is what makes interpret-mode bit-exactness a contract rather
# than a coincidence (same ops, same order, same operands).
# ---------------------------------------------------------------------------
def _flash_update(m, l, acc, q, k, v, kpos, length):
    """One online-softmax block update.

    m, l [rep, 1]; acc [rep, dh]; q [rep, dh]; k, v [bk, dh] (all f32);
    kpos [1, bk] absolute cache positions; length: scalar valid count.
    """
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)   # [rep, bk]
    s = jnp.where(kpos < length, s, _NEG)
    m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
    corr = jnp.exp(m - m_new)
    p = jnp.exp(s - m_new)
    l_new = l * corr + jnp.sum(p, axis=-1, keepdims=True)
    acc_new = acc * corr + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    return m_new, l_new, acc_new


def _block_positions(blk, bk: int):
    """Absolute cache positions [1, bk] of KV block ``blk`` (2-D iota along
    lanes, the layout of the score row it masks)."""
    return blk * bk + jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1)


def _dequant_block(scheme_name: str, packed, scales):
    """One KV head's block: packed [bk, dh/4] int32 + scales [bk, 1] ->
    f32 [bk, dh].

    Unpacks with the shared ``kv_unpack_codes`` codec (shift/mask and a
    lane concatenation — Pallas-safe), then decodes arithmetically (two's
    complement / E4M3 with DAZ, NaN-as-zero) — identical values to the
    quant.schemes LUT path, gather-free in-kernel.
    """
    codes = kv_unpack_codes(packed)
    vals = _decode_int(codes, 8) if scheme_name == "int8" \
        else _decode_fp8_e4m3(codes)
    return vals * scales


def _prep_queries(q, hk: int):
    """q [B, 1, H, Dh] bf16 -> prescaled f32 [B, Hk, rep, Dh] (grouped-GQA
    layout; head h = group h//rep, repeat h%rep — as in _attend_dense)."""
    b, sq, h, dh = q.shape
    assert sq == 1, "decode kernel is the Sq == 1 path"
    scale = jnp.float32(1.0 / math.sqrt(dh))
    return (q[:, 0].astype(jnp.float32) * scale).reshape(b, hk, h // hk, dh)


def _pick_bk(sk: int, bk=None) -> int:
    """Largest power-of-two KV block (<= 512) dividing the slab capacity
    (pool capacities are prefill-chunk aligned, so this is never 1 in
    practice)."""
    if bk is not None:
        assert sk % bk == 0, (sk, bk)
        return bk
    for cand in (512, 256, 128, 64, 32, 16, 8, 4, 2, 1):
        if sk % cand == 0:
            return cand
    raise AssertionError(sk)


# ---------------------------------------------------------------------------
# Kernel bodies (bf16 slab / packed-quantized slab)
# ---------------------------------------------------------------------------
def _decode_step(len_ref, q_ref, head_kv, o_ref, m_ref, l_ref, bk: int):
    """One KV block of one slot row: the flash update of every KV head.
    ``head_kv(h)`` loads head h's (k, v) block as f32 [bk, dh]."""
    row, blk = pl.program_id(0), pl.program_id(1)

    @pl.when(blk == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        o_ref[...] = jnp.zeros_like(o_ref)

    kpos = _block_positions(blk, bk)
    length = len_ref[row]
    for h in range(q_ref.shape[1]):
        k, v = head_kv(h)
        m, l, acc = _flash_update(m_ref[0, h], l_ref[0, h], o_ref[0, h],
                                  q_ref[0, h], k, v, kpos, length)
        m_ref[0, h] = m
        l_ref[0, h] = l
        o_ref[0, h] = acc

    @pl.when(blk == pl.num_programs(1) - 1)
    def _normalize():
        o_ref[...] = o_ref[...] / jnp.maximum(l_ref[...], 1e-30)


def _decode_bf16_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, *,
                        bk: int):
    def head_kv(h):
        return (k_ref[0, :, h, :].astype(jnp.float32),      # [bk, dh]
                v_ref[0, :, h, :].astype(jnp.float32))
    _decode_step(len_ref, q_ref, head_kv, o_ref, m_ref, l_ref, bk)


def _decode_quant_kernel(len_ref, q_ref, kp_ref, ks_ref, vp_ref, vs_ref,
                         o_ref, m_ref, l_ref, *, bk: int, scheme_name: str):
    def head_kv(h):
        return (_dequant_block(scheme_name, kp_ref[0, :, h, :],
                               ks_ref[0, :, h:h + 1]),
                _dequant_block(scheme_name, vp_ref[0, :, h, :],
                               vs_ref[0, :, h:h + 1]))
    _decode_step(len_ref, q_ref, head_kv, o_ref, m_ref, l_ref, bk)


# ---------------------------------------------------------------------------
# Entry point
# ---------------------------------------------------------------------------
def gqa_decode_attention(q, k_cache, v_cache, kv_valid_len, *, bk=None,
                         interpret=None):
    """Fused decode attention over a (possibly quantized) KV pool slab.

    q [B, 1, H, Dh] bf16; k_cache/v_cache either bf16 [B, Sk, Hk, Dh] or
    ``QuantizedKV`` (packed [B, Sk, Hk, Dh/4] int32 + scales [B, Sk, Hk]);
    kv_valid_len [B] committed positions per slot (the just-written token
    included).  Returns [B, 1, H, Dh] in q.dtype.  ``interpret``: None
    follows the platform (``repro.kernels.resolve_interpret``).
    """
    b, sq, h, dh = q.shape
    quant = isinstance(k_cache, QuantizedKV)
    if quant:
        sk, hk = k_cache.packed.shape[1], k_cache.packed.shape[2]
    else:
        sk, hk = k_cache.shape[1], k_cache.shape[2]
    rep = h // hk
    qg = _prep_queries(q, hk)
    bk = _pick_bk(sk, bk)
    grid = (b, sk // bk)
    lens = jnp.asarray(kv_valid_len, jnp.int32).reshape(b)

    len_spec = pl.BlockSpec(memory_space=pltpu.SMEM)
    q_spec = pl.BlockSpec((1, hk, rep, dh), lambda bi, ki: (bi, 0, 0, 0))
    o_spec = pl.BlockSpec((1, hk, rep, dh), lambda bi, ki: (bi, 0, 0, 0))
    ml_spec = pl.BlockSpec((1, hk, rep, 1), lambda bi, ki: (bi, 0, 0, 0))
    out_shape = (jax.ShapeDtypeStruct((b, hk, rep, dh), jnp.float32),
                 jax.ShapeDtypeStruct((b, hk, rep, 1), jnp.float32),
                 jax.ShapeDtypeStruct((b, hk, rep, 1), jnp.float32))

    if quant:
        dw = k_cache.packed.shape[-1]
        kv_spec = pl.BlockSpec((1, bk, hk, dw), lambda bi, ki: (bi, ki, 0, 0))
        sc_spec = pl.BlockSpec((1, bk, hk), lambda bi, ki: (bi, ki, 0))
        kernel = functools.partial(_decode_quant_kernel, bk=bk,
                                   scheme_name=k_cache.scheme_name)
        in_specs = [len_spec, q_spec, kv_spec, sc_spec, kv_spec, sc_spec]
        args = (lens, qg, k_cache.packed, k_cache.scales,
                v_cache.packed, v_cache.scales)
    else:
        kv_spec = pl.BlockSpec((1, bk, hk, dh), lambda bi, ki: (bi, ki, 0, 0))
        kernel = functools.partial(_decode_bf16_kernel, bk=bk)
        in_specs = [len_spec, q_spec, kv_spec, kv_spec]
        args = (lens, qg, k_cache, v_cache)

    out, _, _ = pl.pallas_call(
        kernel, grid=grid, in_specs=in_specs,
        out_specs=(o_spec, ml_spec, ml_spec), out_shape=out_shape,
        interpret=resolve_interpret(interpret), name="decode_attention",
    )(*args)
    return out.reshape(b, 1, h, dh).astype(q.dtype)


# ---------------------------------------------------------------------------
# Mesh entry point: the kernel under shard_map (DESIGN.md §14)
# ---------------------------------------------------------------------------
def decode_attention_shard_specs(mesh, b: int, hk: int, quant: bool):
    """(q_spec, kv_spec, len_spec, out_spec) for sharding the decode
    kernel over a serving mesh: slot rows on the data axis, KV heads on
    'model' — the ``serve_pool_pspec`` layout, with the same divisibility
    guards (a non-dividing axis stays replicated; redundant compute, never
    a wrong shape).  ``kv_spec`` mirrors the cache pytree: a
    ``QuantizedKV`` node of specs for packed pools, a bare spec for bf16.

    The query head axis shards with the KV head axis: ``_prep_queries``
    groups query heads contiguously per KV head (h -> group h // rep), so
    an even split of H lands each shard exactly the query heads of its own
    KV heads.
    """
    from jax.sharding import PartitionSpec as P
    axes = dict(mesh.shape)
    dp, tp = axes.get("data", 1), axes.get("model", 1)
    slot_ax = "data" if dp > 1 and b % dp == 0 and b >= dp else None
    head_ax = "model" if tp > 1 and hk % tp == 0 and hk >= tp else None
    q_spec = P(slot_ax, None, head_ax, None)
    if quant:
        kv_spec = QuantizedKV(P(slot_ax, None, head_ax, None),
                              P(slot_ax, None, head_ax), "")
    else:
        kv_spec = P(slot_ax, None, head_ax, None)
    return q_spec, kv_spec, P(slot_ax), q_spec


def sharded_gqa_decode_attention(q, k_cache, v_cache, kv_valid_len, *, mesh,
                                 bk=None, interpret=None):
    """``gqa_decode_attention`` under ``shard_map`` over the serving mesh.

    Each shard runs the unmodified kernel on its local
    [B/dp, Sk, Hk/tp, ...] slab — the softmax is per (row, head) and the
    KV sequence axis stays whole, so there is no cross-shard collective
    and the sharded output is BITWISE identical to the meshless kernel
    (hence to ``ref.decode_attention_ref``, the §9 contract).

    When NO axis actually shards (the divisibility guards leave every
    spec replicated — e.g. 2 KV heads on an 8-way model axis with dp=1),
    the kernel runs bare: GSPMD keeps a replicated custom call replicated,
    whereas a degenerate all-replicated shard_map only perturbs the
    partitioner's choices around it (observed as ulp-level drift in the
    surrounding matmuls at tp=8).
    """
    b, _, h, dh = q.shape
    quant = isinstance(k_cache, QuantizedKV)
    hk = (k_cache.packed if quant else k_cache).shape[2]
    q_spec, kv_spec, len_spec, out_spec = decode_attention_shard_specs(
        mesh, b, hk, quant)
    if all(ax is None for ax in q_spec):   # nothing shards: skip shard_map
        return gqa_decode_attention(q, k_cache, v_cache, kv_valid_len,
                                    bk=bk, interpret=interpret)
    if quant:  # carry the real scheme name so spec/cache trees match
        kv_spec = QuantizedKV(kv_spec.packed, kv_spec.scales,
                              k_cache.scheme_name)
    fn = jax.shard_map(
        functools.partial(gqa_decode_attention, bk=bk, interpret=interpret),
        mesh=mesh,
        in_specs=(q_spec, kv_spec, kv_spec, len_spec),
        out_specs=out_spec, check_vma=False)
    return fn(q, k_cache, v_cache, jnp.asarray(kv_valid_len, jnp.int32))
