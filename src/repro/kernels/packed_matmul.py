"""Pallas TPU kernels: mixed-precision matmul/GEMV over packed weights.

This is the TPU realization of the paper's Section VI GEMV engine: weights
live in HBM as packed sub-byte codes (8x INT4/FP4 or 4x INT8/FP8 per int32
word — the analogue of the 512-bit HBM channel words feeding XtraMAC
chains), are streamed block-by-block into VMEM, decoded one code plane at
a time (integer and FP8 codes with XtraMAC Stage-1 semantics: DAZ,
implicit-one restore; FP4 codes as OCP MX E2M1, subnormals included),
scaled, rounded to bf16 and fed to the MXU with f32 accumulation — the
same bf16 weight operand the jnp path (``quant.schemes.dequantize``) makes,
without writing it to HBM.

Kernels:
  * ``packed_matmul``  A[M,K] bf16 x packed W[K,N] -> f32 [M,N]
                       grid (M/bm, N/bn, K/bk), revisiting-accumulate on k;
                       inside a block, chunks of words decoded plane-major
                       against activations permuted to the same K order
  * ``w8a8_matmul``    int8 x int8 -> int32 MXU accumulate -> scale epilogue

The default block plan (``packed_block_plan``) is sized for decode-shaped
M: every grid step streams about ``STEP_BYTES`` of packed words.  Block
shapes are MXU/VMEM aligned (bn a multiple of 128, bk a multiple of the
packing group), validated under interpret mode on CPU and compiled for a
v5e in ``tests/test_tpu_compile.py``.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret
from repro.quant.schemes import (
    QuantScheme, QuantizedLinearWeights, effective_group,
)


# ---------------------------------------------------------------------------
# In-kernel arithmetic decode (no gathers — TPU-friendly)
# ---------------------------------------------------------------------------
def _decode_int(codes, bits: int):
    half = 1 << (bits - 1)
    return jnp.where(codes >= half, codes - (1 << bits), codes).astype(jnp.float32)


def _decode_fp4_e2m1(codes):
    """OCP MX v1.0 E2M1: exponent field 0 holds 0 and the subnormal 0.5
    (codes 1 and 9 read +-0.5, not the FPGA MAC model's DAZ zero).

    Built as f32 bits with integer ops on the int32 codes (bits above the
    code's four are ignored): the magnitude bits (e, m) at bits 22-24 plus
    the exponent bias 126 read 2^(e-1) * (1 + m/2) for e >= 1; for
    exponent field 0 the select takes (m << 22) * 252 instead: 0, or 0.5
    as the f32 bits 126 << 23, never an f32 subnormal, which the TPU
    flushes; the sign bit goes to bit 31."""
    mag = (codes & 7) << 22
    mag = jnp.where(mag >= 2 << 22, mag + (126 << 23), mag * 252)
    return jax.lax.bitcast_convert_type(mag | (codes & 8) << 28, jnp.float32)


def _decode_fp8_e4m3(codes):
    s = (codes >> 7) & 1
    e = (codes >> 3) & 0xF
    m = codes & 7
    nan = (e == 0xF) & (m == 7)
    mag = jnp.where(e == 0, 0.0,
                    (8 + m).astype(jnp.float32) * jnp.exp2((e - 10).astype(jnp.float32)))
    mag = jnp.where(nan, 0.0, mag)  # weights never encode NaN; decode as 0
    return jnp.where(s == 1, -mag, mag)


def decode_codes_arith(scheme: QuantScheme, codes):
    if scheme.weight_format.startswith("int"):
        return _decode_int(codes, scheme.weight_bits)
    if scheme.weight_format == "fp4_e2m1":
        return _decode_fp4_e2m1(codes)
    if scheme.weight_format == "fp8_e4m3":
        return _decode_fp8_e4m3(codes)
    raise ValueError(scheme.weight_format)


def _code_plane(words, i: int, scheme: QuantScheme):
    """Code ``i`` of every packed word (bits [i*bits, (i+1)*bits)) decoded
    to f32.  Integer codes sign-extend by a left then an arithmetic right
    shift; E2M1 codes are shifted down and decoded without a mask (the
    decode reads only their low four bits); FP8 codes go through
    ``decode_codes_arith``."""
    bits = scheme.weight_bits
    if scheme.weight_format.startswith("int"):
        return ((words << (32 - bits * (i + 1))) >> (32 - bits)) \
            .astype(jnp.float32)
    if scheme.weight_format == "fp4_e2m1":
        return _decode_fp4_e2m1(words >> (bits * i))
    return decode_codes_arith(scheme, (words >> (bits * i)) & ((1 << bits) - 1))


def _scale_rows(scales, cw: int):
    """[ns, bn] scale rows -> [cw, bn]: each row repeated for the cw // ns
    consecutive words it covers."""
    ns, bn = scales.shape
    return jnp.broadcast_to(scales[:, None, :], (ns, cw // ns, bn)) \
        .reshape(cw, bn)


def dequant_planes(words, scales, scheme: QuantScheme):
    """One chunk's MXU operands: int32 words [cw, bn] and their scale rows
    [ns, bn] (one row: a scale for every word) -> ``per`` bf16 [cw, bn]
    code planes, plane ``i`` holding code ``i`` of every word.  No code is
    interleaved back into K order; the activations are permuted to match
    instead (``chunk_activations``).  Each decoded value is scaled in f32
    and rounded once to bf16, as ``quant.schemes.dequantize(dtype=bf16)``
    rounds it."""
    cw = words.shape[0]
    if scales.shape[0] > 1:
        scales = _scale_rows(scales, cw)
    return [(_code_plane(words, i, scheme) * scales).astype(jnp.bfloat16)
            for i in range(32 // scheme.weight_bits)]


def chunk_product(acc, x, words, scales, scheme: QuantScheme):
    """acc [bm, bn] f32 + one chunk's product: x [bm, cw * per] (the chunk's
    slab of ``chunk_activations``) against its dequantized code planes,
    one bf16 dot per plane, summed into ``acc`` in plane order."""
    cw = words.shape[0]
    for i, plane in enumerate(dequant_planes(words, scales, scheme)):
        acc = acc + jnp.dot(x[:, i * cw:(i + 1) * cw], plane,
                            preferred_element_type=jnp.float32)
    return acc


def chunk_activations(x, cw: int, per: int):
    """x [M, K] -> bf16 [K / (cw * per), M, cw * per]: one slab per chunk of
    ``cw`` packed words, its columns in the plane-major order of
    ``dequant_planes`` (column ``i * cw + w`` is K index ``w * per + i`` of
    the chunk)."""
    m, k = x.shape
    u = cw * per
    return x.astype(jnp.bfloat16).reshape(m, k // u, cw, per) \
        .transpose(1, 0, 3, 2).reshape(k // u, m, u)


# ---------------------------------------------------------------------------
# packed matmul kernel
# ---------------------------------------------------------------------------
def _packed_matmul_kernel(x_ref, w_ref, s_ref, o_ref, *scratch,
                          scheme: QuantScheme, cw: int, ns: int):
    """One (M, N, K) block: the K block's chunks of ``cw`` words, each
    dequantized to bf16 code planes and dotted with f32 accumulation.
    ``ns``: scale rows per chunk, 0 when one scale row serves the whole
    block.  ``scratch``: a VMEM [cw, bn] f32 buffer when a scale row
    covers fewer words than a vreg has sublanes (mxfp4: 4 of 8); the
    chunk's rows are broadcast into it once and read by every plane,
    where a broadcast in registers is redone, through strided stores and
    shuffled loads, for each plane."""
    @pl.when(pl.program_id(2) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    def chunk(c, acc):
        words = w_ref[pl.ds(pl.multiple_of(c * cw, cw), cw), :]
        scales = s_ref[...] if ns == 0 else \
            s_ref[pl.ds(pl.multiple_of(c * ns, ns), ns), :]
        if scratch:
            scratch[0][...] = _scale_rows(scales, cw)
            scales = scratch[0][...]
        return chunk_product(acc, x_ref[c], words, scales, scheme)

    o_ref[...] += jax.lax.fori_loop(0, x_ref.shape[0], chunk,
                                    jnp.zeros(o_ref.shape, jnp.float32))


def _fit_block(dim: int, want: int, quantum: int = 1) -> int:
    """Largest block <= ``want`` that divides ``dim`` and is a multiple of
    ``quantum`` (the code-packing word / scale group).  Falls back to
    ``dim`` itself (one block) when no smaller aligned divisor exists —
    irregular dims cost tiling efficiency, never correctness."""
    want = min(want, dim)
    for cand in range(want - want % quantum, 0, -quantum):
        if dim % cand == 0:
            return cand
    return dim


# Default block plan, set for the serving shapes: M is the decode slots or
# a prefill chunk (16 in granite-8b.batch-decode).  A grid step costs about
# 0.35 us of its own, so a step streams STEP_BYTES of packed words.  The
# activation block (bm x bk bf16) stays under X_BLOCK_BYTES, so at decode
# M a block spans all of K and each output block is written once; the f32
# output block (bm x bn) stays under OUT_BLOCK_BYTES, which keeps large M
# inside VMEM.  CHUNK_WORDS word rows are decoded at a time.
STEP_BYTES = 1 << 20
X_BLOCK_BYTES = 2 << 20
OUT_BLOCK_BYTES = 1 << 20
MAX_BM = 256
CHUNK_WORDS = 128


def packed_block_plan(m: int, k: int, n: int, scheme: QuantScheme, *,
                      bm: Optional[int] = None, bn: Optional[int] = None,
                      bk: Optional[int] = None):
    """The (bm, bn, bk) tiling ``packed_matmul`` uses for these shapes; a
    block given is an upper bound, one left out takes the default plan.

    Exported so the bit-exact oracle (``ref.packed_matmul_tiled_ref``) can
    replay the exact same grid: per-element results depend on the K-block
    accumulation order and the per-tile dot shapes, so oracle and kernel
    must agree on the plan, not just the math."""
    group = effective_group(scheme.group_size, k)
    per = 32 // scheme.weight_bits
    bm = _fit_block(m, bm or MAX_BM)
    # K blocks land on scale-group boundaries when the matrix has several
    # groups — eight at a time, so a block's scale rows fill the TPU's
    # 8-row tile (or the block spans all of K) — else (per-channel: one
    # global scale row) on word boundaries.  N blocks keep to the 128-lane
    # tile when N allows it.
    if bk is None:
        bk = X_BLOCK_BYTES // (2 * bm)
    if group < k:
        quantum = 8 * group
        bk = max(bk, quantum)
    else:
        quantum = per
    bk = _fit_block(k, bk, quantum)
    if bn is None:
        bn = min(-(-8 * STEP_BYTES // (bk * scheme.weight_bits) // 128),
                 max(1, OUT_BLOCK_BYTES // (4 * bm) // 128)) * 128
    return bm, _fit_block(n, bn, 128 if n % 128 == 0 else 1), bk


def chunk_words(bk: int, k: int, scheme: QuantScheme) -> int:
    """Word rows per inner chunk of a K block: at most ``CHUNK_WORDS``,
    dividing the block, and, when the block has several scale groups,
    holding them eight at a time (the 8-row tile of the scale block)."""
    per = 32 // scheme.weight_bits
    group = effective_group(scheme.group_size, k)
    return _fit_block(bk // per, CHUNK_WORDS,
                      8 * group // per if group < bk else 1)


def packed_shapes_legal(m: int, k: int, n: int, scheme: QuantScheme) -> bool:
    """Whether (possibly shard-local) shapes can run the packed kernel:
    K must pack whole int32 words and whole scale groups.  The per-site
    fallback predicate of the dispatch (kernels/ops.py)."""
    if m < 1 or n < 1 or k < 1:
        return False
    per = 32 // scheme.weight_bits
    return k % per == 0 and k % effective_group(scheme.group_size, k) == 0


@functools.partial(
    jax.jit,
    static_argnames=("scheme_name", "k", "n", "bm", "bn", "bk", "interpret",
                     "name"),
)
def _packed_matmul_impl(x, packed, scales, *, scheme_name: str, k: int, n: int,
                        bm: int, bn: int, bk: int, interpret: bool,
                        name: str):
    from repro.quant.schemes import get_scheme
    scheme = get_scheme(scheme_name)
    m = x.shape[0]
    per = 32 // scheme.weight_bits
    group = effective_group(scheme.group_size, k)
    cw = chunk_words(bk, k, scheme)
    u = cw * per
    if group < bk:
        ns = u // group
        scale_spec = pl.BlockSpec((bk // group, bn), lambda i, j, l: (l, j))
    else:   # one scale row (per-channel or a single group) for every block
        ns = 0
        scale_spec = pl.BlockSpec((1, bn), lambda i, j, l: (0, j))
    kernel = functools.partial(_packed_matmul_kernel, scheme=scheme, cw=cw,
                               ns=ns)
    return pl.pallas_call(
        kernel,
        grid=(m // bm, n // bn, k // bk),
        in_specs=[
            pl.BlockSpec((bk // u, bm, u), lambda i, j, l: (l, i, 0)),
            pl.BlockSpec((bk // per, bn), lambda i, j, l: (l, j)),
            scale_spec,
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, l: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        scratch_shapes=[pltpu.VMEM((cw, bn), jnp.float32)]
        if ns and cw // ns < 8 else [],
        interpret=interpret,
        name=name,
    )(chunk_activations(x, cw, per), packed, scales)


def packed_matmul(x, qw: QuantizedLinearWeights, *, bm: Optional[int] = None,
                  bn: Optional[int] = None, bk: Optional[int] = None,
                  interpret=None, name: str = "packed_matmul"):
    """x [M, K] (bf16) @ packed W [K, N] -> f32 [M, N]: bf16 operands on the
    MXU, f32 accumulation.  Blocks default to ``packed_block_plan``'s;
    ``name`` is the kernel's stable name in profiles and op metadata."""
    k, n = qw.shape
    m = x.shape[0]
    scheme = qw.scheme
    assert scheme.packed, "packed_matmul requires a sub-byte scheme"
    assert packed_shapes_legal(m, k, n, scheme), (m, k, n, scheme.name)
    bm, bn, bk = packed_block_plan(m, k, n, scheme, bm=bm, bn=bn, bk=bk)
    assert m % bm == 0 and n % bn == 0 and k % bk == 0, (m, n, k, bm, bn, bk)
    return _packed_matmul_impl(
        x, qw.packed, qw.scales, scheme_name=scheme.name, k=k, n=n,
        bm=bm, bn=bn, bk=bk, interpret=resolve_interpret(interpret),
        name=name,
    )


def packed_gemv(x, qw: QuantizedLinearWeights, *, bn: Optional[int] = None,
                bk: Optional[int] = None, interpret=None):
    """Decode-shape GEMV: x [B, K] with small B (the paper's Section VI-C),
    all rows in one block."""
    return packed_matmul(x, qw, bm=x.shape[0], bn=bn, bk=bk,
                         interpret=interpret, name="packed_gemv")


# ---------------------------------------------------------------------------
# W8A8: INT8 x INT8 -> INT32 (the paper's integer accumulate path)
# ---------------------------------------------------------------------------
def _w8a8_kernel(x_ref, w_ref, o_ref):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    # exact INT8 x INT8 -> INT32 accumulation (the paper's integer adder path)
    o_ref[...] += jax.lax.dot_general(
        x_ref[...].astype(jnp.int32), w_ref[...].astype(jnp.int32),
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def _w8a8_impl(x_codes, w_codes, *, bm, bn, bk, interpret):
    m, k = x_codes.shape
    n = w_codes.shape[1]
    grid = (m // bm, n // bn, k // bk)
    return pl.pallas_call(
        _w8a8_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, l: (i, l)),
            pl.BlockSpec((bk, bn), lambda i, j, l: (l, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, l: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.int32),
        interpret=interpret,
        name="w8a8_matmul",
    )(x_codes, w_codes)


def w8a8_matmul(x_codes, x_scale, w_codes, w_scales, *, bm: int = 128,
                bn: int = 128, bk: int = 512, interpret=None):
    """INT8 codes x INT8 codes -> exact INT32 accumulate -> f32 descale.

    x_codes [M, K] int8 (per-tensor scale x_scale), w_codes [K, N] int8
    (per-channel scales [1, N]).  Output f32 [M, N] already descaled.
    """
    m, k = x_codes.shape
    n = w_codes.shape[1]
    bm, bn, bk = _fit_block(m, bm), _fit_block(n, bn), _fit_block(k, bk)
    assert m % bm == 0 and n % bn == 0 and k % bk == 0
    acc = _w8a8_impl(x_codes, w_codes, bm=bm, bn=bn, bk=bk,
                     interpret=resolve_interpret(interpret))
    return acc.astype(jnp.float32) * (w_scales * x_scale)
