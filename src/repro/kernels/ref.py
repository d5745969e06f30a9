"""Pure-jnp oracles for every Pallas kernel (the correctness ground truth).

Each function computes the same mathematical result as its kernel twin via
plain jnp (dequantize -> dense matmul), with f32 accumulation.  The
bit-level packing oracle delegates to core.packing (numpy int64 — exact).
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from repro.core.packing import LanePlan, packed_multiply
from repro.quant.schemes import QuantizedLinearWeights, dequantize


def packed_matmul_ref(x, qw: QuantizedLinearWeights):
    """x [M, K] bf16 @ packed W [K, N] -> f32 [M, N] (dequant-then-matmul).

    The packed kernels' contract: the weight dequantized in f32 and rounded
    once to bf16 (``dequantize(dtype=bf16)``), bf16 operands, f32
    accumulation."""
    w = dequantize(qw, dtype=jnp.bfloat16)
    return jnp.dot(x.astype(jnp.bfloat16), w,
                   preferred_element_type=jnp.float32)


def packed_gemv_ref(x, qw: QuantizedLinearWeights):
    """GEMV special case (decode shapes): x [B, K], B small."""
    return packed_matmul_ref(x, qw)


# Kernel vs ``packed_matmul_tiled_ref`` agree to this share of the output's
# largest magnitude.  Interpret mode compiles the kernel body as one XLA
# CPU program, which may reorder a tile's f32 sums, while the oracle runs
# op by op; a shard-local tile shape compiles to yet another program.
# Observed differences reach about 2^-20 of the largest output (K = 512,
# sharded); a wrong plan, scale or decode is off by orders of magnitude
# more.
TILED_REF_ATOL = 2.0 ** -16


def packed_matmul_tiled_ref(x, qw: QuantizedLinearWeights, *, bm=None,
                            bn=None, bk=None):
    """BIT-exact oracle for ``packed_matmul``: replays the kernel's grid.

    Same tiling (``packed_block_plan``, ``chunk_words``), same chunked
    activations (``chunk_activations``), the same chunk product
    (``chunk_product`` — shift/mask decode, DAZ, per-group scaling, one bf16
    dot per code plane, shared with the kernel body) and the same chunk
    and K-block accumulation order, as plain jnp loops.  f32
    sums are not associative, so agreeing on the *plan* is what upgrades
    the dequant-LUT ``packed_matmul_ref`` tolerance contract to a bitwise
    one (the DESIGN.md §14 analogue of the §9 decode-attention contract).
    """
    from .packed_matmul import (chunk_activations, chunk_product,
                                chunk_words, packed_block_plan)
    from repro.quant.schemes import effective_group

    scheme = qw.scheme
    k, n = qw.shape
    m = x.shape[0]
    bm, bn, bk = packed_block_plan(m, k, n, scheme, bm=bm, bn=bn, bk=bk)
    per = 32 // scheme.weight_bits
    group = effective_group(scheme.group_size, k)
    cw = chunk_words(bk, k, scheme)
    u = cw * per
    ns = u // group if group < bk else 0
    xc = chunk_activations(x, cw, per)                    # [K/u, M, u]
    out = jnp.zeros((m, n), jnp.float32)
    for i in range(m // bm):
        rows = slice(i * bm, (i + 1) * bm)
        for j in range(n // bn):
            cols = slice(j * bn, (j + 1) * bn)
            tile = jnp.zeros((bm, bn), jnp.float32)
            for l in range(k // bk):
                acc = jnp.zeros((bm, bn), jnp.float32)
                for c in range(l * bk // u, (l + 1) * bk // u):
                    words = qw.packed[c * cw:(c + 1) * cw, cols]
                    scales = qw.scales[c * ns:(c + 1) * ns, cols] if ns \
                        else qw.scales[0:1, cols]
                    acc = chunk_product(acc, xc[c, rows], words, scales,
                                        scheme)
                tile = tile + acc
            out = out.at[rows, cols].set(tile)
    return out


def _shard_qw(qw: QuantizedLinearWeights, tp: int, j: int, dim: int):
    """Shard ``j`` of ``tp`` of a packed weight along logical dim (0=K,
    1=N) — split at the joint code-word/scale-group boundaries that
    ``partitioning.param_specs`` enforces for K."""
    k, n = qw.shape
    if dim == 1:
        nl = n // tp
        return QuantizedLinearWeights(
            qw.scheme, qw.packed[:, j * nl:(j + 1) * nl],
            qw.scales[:, j * nl:(j + 1) * nl], (k, nl))
    kp = qw.packed.shape[0] // tp
    ks = qw.scales.shape[0] // tp
    kl = k // tp
    return QuantizedLinearWeights(
        qw.scheme, qw.packed[j * kp:(j + 1) * kp],
        qw.scales[j * ks:(j + 1) * ks], (kl, n))


def sharded_packed_matmul_ref(x, qw: QuantizedLinearWeights, *, tp: int,
                              shard_dim: int, bm=None, bn=None, bk=None):
    """Oracle for the shard_map'd weight-path kernel (kernels/ops.py).

    Decomposes exactly as the mesh dispatch does — N sharded over 'model'
    (concatenate local results), or K sharded at joint word/scale-group
    boundaries (f32 partials + psum) — and runs the bit-exact tiled oracle
    per shard.  The N-sharded path is bitwise identical to the meshless
    kernel (the K loop is untouched); the K-sharded path matches the
    shard_map'd kernel's psum association (left-to-right over shards).
    """
    parts = [packed_matmul_tiled_ref(
        x if shard_dim == 1 else x[:, (x.shape[1] // tp) * j:
                                   (x.shape[1] // tp) * (j + 1)],
        _shard_qw(qw, tp, j, shard_dim), bm=bm, bn=bn, bk=bk)
        for j in range(tp)]
    if shard_dim == 1:
        return jnp.concatenate(parts, axis=1)
    out = parts[0]
    for p in parts[1:]:
        out = out + p
    return out


def w8a8_matmul_ref(x_codes, x_scale, w_codes, w_scales):
    """INT8 x INT8 -> INT32 accumulate -> scale epilogue (SmoothQuant MAC).

    x_codes [M, K] int8; w_codes [K, N] int8; w_scales [1, N] f32.
    INT32 accumulation is exact, matching the paper's integer adder path.
    """
    acc = jnp.dot(
        x_codes.astype(jnp.int32), w_codes.astype(jnp.int32),
        preferred_element_type=jnp.int32,
    )
    # same association as the kernel epilogue (floats are non-associative)
    return acc.astype(jnp.float32) * (w_scales * x_scale)


def virtual_dsp_ref(plan: LanePlan, a_mags: np.ndarray, b_mags: np.ndarray):
    """Lane products via the exact int64 virtual-DSP packing (Eqs. 9-11)."""
    return packed_multiply(plan, np.asarray(a_mags), np.asarray(b_mags))


def decode_attention_ref(q, k_cache, v_cache, kv_valid_len, *, bk=None):
    """Split-KV online-softmax oracle for ``kernels/decode_attention.py``.

    Runs the *same* per-block update (`_flash_update`, shared with the
    kernel body) as a plain jnp loop over (row, KV-head, block) — so the
    interpret-mode kernel is BIT-exact against this function on bf16 and
    quantized KV alike (the DESIGN.md §9 equivalence contract).  Agreement
    with the production einsum path (`models/attention.attend`) is to bf16
    rounding tolerance only: that path rounds scores and probabilities
    through bf16 storage between dispatches, this one stays f32 after the
    loads.
    """
    from repro.quant.kv_cache import QuantizedKV

    from .decode_attention import (_NEG, _block_positions, _dequant_block,
                                   _flash_update, _pick_bk, _prep_queries)

    b, _, h, dh = q.shape
    quant = isinstance(k_cache, QuantizedKV)
    if quant:
        sk, hk = k_cache.packed.shape[1], k_cache.packed.shape[2]
    else:
        sk, hk = k_cache.shape[1], k_cache.shape[2]
    rep = h // hk
    qg = _prep_queries(q, hk)
    bk = _pick_bk(sk, bk)
    lens = jnp.asarray(kv_valid_len, jnp.int32)

    rows = []
    for bi in range(b):
        heads = []
        for hi in range(hk):
            m = jnp.full((rep, 1), _NEG, jnp.float32)
            l = jnp.zeros((rep, 1), jnp.float32)
            acc = jnp.zeros((rep, dh), jnp.float32)
            for blk in range(sk // bk):
                sl = slice(blk * bk, (blk + 1) * bk)
                if quant:
                    k = _dequant_block(k_cache.scheme_name,
                                       k_cache.packed[bi, sl, hi],
                                       k_cache.scales[bi, sl, hi][:, None])
                    v = _dequant_block(v_cache.scheme_name,
                                       v_cache.packed[bi, sl, hi],
                                       v_cache.scales[bi, sl, hi][:, None])
                else:
                    k = k_cache[bi, sl, hi].astype(jnp.float32)
                    v = v_cache[bi, sl, hi].astype(jnp.float32)
                m, l, acc = _flash_update(m, l, acc, qg[bi, hi], k, v,
                                          _block_positions(blk, bk), lens[bi])
            heads.append(acc / jnp.maximum(l, 1e-30))
        rows.append(jnp.stack(heads))                     # [hk, rep, dh]
    out = jnp.stack(rows)                                 # [b, hk, rep, dh]
    return out.reshape(b, 1, h, dh).astype(q.dtype)


def paged_decode_attention_ref(q, k_arena, v_arena, page_table,
                               kv_valid_len, *, bk=None):
    """Paged-attention oracle (DESIGN.md §15): gather each slot's virtual
    KV slab from the page arena through its page table, then run the
    UNCHANGED split-KV oracle on the gathered slabs.

    This *is* the paged serving contract in one line: paged attention =
    page gather + slab attention.  The production path does exactly this
    inside its jitted steps (``quant.kv_cache.gather_pages`` feeding the
    einsum path or the Pallas decode kernel), so the kernel is bit-exact
    against this oracle whenever it is bit-exact against
    ``decode_attention_ref`` on the gathered slab — garbage pages gathered
    into positions >= ``kv_valid_len`` are masked to exact zero by the
    flash update, identically in both.

    ``k_arena`` / ``v_arena``: [n_pages, page_size, hk, dh] (bf16 or
    ``QuantizedKV``).  ``page_table``: [n_slots, pages_per_slot] int32.
    """
    from repro.quant.kv_cache import gather_pages

    table = jnp.asarray(page_table, jnp.int32)
    return decode_attention_ref(q, gather_pages(k_arena, table),
                                gather_pages(v_arena, table),
                                kv_valid_len, bk=bk)


def sharded_decode_attention_ref(q, k_cache, v_cache, kv_valid_len, *,
                                 dp: int = 1, tp: int = 1, bk=None):
    """Oracle for ``sharded_gqa_decode_attention``: decompose the slot and
    KV-head axes exactly as the shard_map specs do (same divisibility
    guards), run ``decode_attention_ref`` per (slot-band, head-band) shard,
    reassemble.  The sharded kernel has no cross-shard collective, so this
    equals the meshless oracle bitwise — computing it shard-by-shard pins
    the decomposition itself, not just the math."""
    from repro.quant.kv_cache import QuantizedKV

    b, _, h, dh = q.shape
    quant = isinstance(k_cache, QuantizedKV)
    hk = (k_cache.packed if quant else k_cache).shape[2]
    rep = h // hk
    nb = dp if (dp > 1 and b % dp == 0 and b >= dp) else 1
    nh = tp if (tp > 1 and hk % tp == 0 and hk >= tp) else 1
    bb, hh = b // nb, hk // nh
    lens = jnp.asarray(kv_valid_len, jnp.int32)

    def slab(c, bs, hs):
        if quant:
            return QuantizedKV(c.packed[bs][:, :, hs], c.scales[bs][:, :, hs],
                               c.scheme_name)
        return c[bs][:, :, hs]

    rows = []
    for i in range(nb):
        bs = slice(i * bb, (i + 1) * bb)
        cols = []
        for j in range(nh):
            hs = slice(j * hh, (j + 1) * hh)
            qs = slice(j * hh * rep, (j + 1) * hh * rep)
            cols.append(decode_attention_ref(
                q[bs][:, :, qs], slab(k_cache, bs, hs), slab(v_cache, bs, hs),
                lens[bs], bk=bk))
        rows.append(jnp.concatenate(cols, axis=2))
    return jnp.concatenate(rows, axis=0)
