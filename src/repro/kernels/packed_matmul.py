"""Pallas TPU kernels: mixed-precision matmul/GEMV over packed weights.

This is the TPU realization of the paper's Section VI GEMV engine: weights
live in HBM as packed sub-byte codes (8x INT4/FP4 or 4x INT8/FP8 per int32
word — the analogue of the 512-bit HBM channel words feeding XtraMAC
chains), are streamed block-by-block into VMEM, unpacked + decoded with
XtraMAC Stage-1 semantics (DAZ, implicit-one restore), scaled, and fed to
the MXU.  Accumulation is f32 (the BF16-accumulate spec lives in core.mac;
tensor-core-style f32 accumulation is strictly more accurate and is what
the MXU provides natively — noted in DESIGN.md).

Kernels:
  * ``packed_matmul``  A[M,K] bf16 x packed W[K,N] -> f32 [M,N]
                       grid (M/bm, N/bn, K/bk), revisiting-accumulate on k
  * ``w8a8_matmul``    int8 x int8 -> int32 MXU accumulate -> scale epilogue

Block shapes are MXU/VMEM aligned by default (bn multiple of 128, bk
multiple of the packing group), validated under interpret mode on CPU and
compiled for a v5e in ``tests/test_tpu_compile.py``.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import resolve_interpret
from repro.quant.schemes import (
    QuantScheme, QuantizedLinearWeights, effective_group,
)


# ---------------------------------------------------------------------------
# In-kernel arithmetic decode (no gathers — TPU-friendly), DAZ semantics
# ---------------------------------------------------------------------------
def _decode_int(codes, bits: int):
    half = 1 << (bits - 1)
    return jnp.where(codes >= half, codes - (1 << bits), codes).astype(jnp.float32)


def _decode_fp4_e2m1(codes):
    s = (codes >> 3) & 1
    e = (codes >> 1) & 3
    m = codes & 1
    mag = jnp.where(e == 0, 0.0,
                    (2 + m).astype(jnp.float32) * jnp.exp2((e - 2).astype(jnp.float32)))
    return jnp.where(s == 1, -mag, mag)


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


def _unpack_block(words, bits: int):
    """int32 [bkw, bn] -> codes [bkw*per, bn] (little-endian along K)."""
    per = 32 // bits
    mask = (1 << bits) - 1
    parts = [(words >> (i * bits)) & mask for i in range(per)]
    stacked = jnp.stack(parts, axis=1)                 # [bkw, per, bn]
    return stacked.reshape(words.shape[0] * per, words.shape[1])


# ---------------------------------------------------------------------------
# packed matmul kernel
# ---------------------------------------------------------------------------
def _packed_matmul_kernel(x_ref, w_ref, s_ref, o_ref, *, scheme: QuantScheme,
                          bk: int, group: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    codes = _unpack_block(w_ref[...], scheme.weight_bits)       # [bk, bn]
    vals = decode_codes_arith(scheme, codes)                    # f32
    ng = bk // group
    scales = s_ref[...]                                         # [ng, bn]
    vals = (vals.reshape(ng, group, vals.shape[-1]) * scales[:, None, :]) \
        .reshape(bk, vals.shape[-1])
    x = x_ref[...].astype(jnp.float32)
    o_ref[...] += jnp.dot(x, vals, preferred_element_type=jnp.float32)


def _pick(block: int, dim: int) -> int:
    return min(block, dim)


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


def packed_block_plan(m: int, k: int, n: int, scheme: QuantScheme, *,
                      bm: int = 128, bn: int = 128, bk: int = 512):
    """The (bm, bn, bk) tiling ``packed_matmul`` uses for these shapes.

    Exported so the bit-exact oracle (``ref.packed_matmul_tiled_ref``) can
    replay the exact same grid: per-element results depend on the K-block
    accumulation order and the per-tile dot shapes, so oracle and kernel
    must agree on the plan, not just the math."""
    group = effective_group(scheme.group_size, k)
    per = 32 // scheme.weight_bits
    # K blocks land on scale-group boundaries when the matrix has several
    # groups — eight at a time, so a block's scale rows fill the TPU's
    # 8-row tile (or the block spans all of K) — else (per-channel: one
    # global scale row) on word boundaries.  N blocks keep to the 128-lane
    # tile when N allows it.
    if group < k:
        quantum = 8 * group
        bk = max(bk, quantum)
    else:
        quantum = per
    return (_fit_block(m, bm), _fit_block(n, bn, 128 if n % 128 == 0 else 1),
            _fit_block(k, bk, quantum))


def packed_shapes_legal(m: int, k: int, n: int, scheme: QuantScheme) -> bool:
    """Whether (possibly shard-local) shapes can run the packed kernel:
    K must pack whole int32 words and whole scale groups.  The per-site
    fallback predicate for mesh dispatch (kernels/ops.py)."""
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
    grid = (m // bm, n // bn, k // bk)
    ng = bk // group if group <= bk else 1
    if group > bk:  # per-channel (group == k): one scale row for all k-blocks
        scale_spec = pl.BlockSpec((1, bn), lambda i, j, l: (0, j))
    else:
        scale_spec = pl.BlockSpec((ng, bn), lambda i, j, l: (l, j))
    kernel = functools.partial(
        _packed_matmul_kernel, scheme=scheme, bk=bk, group=min(group, bk)
    )
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, l: (i, l)),
            pl.BlockSpec((bk // per, bn), lambda i, j, l: (l, j)),
            scale_spec,
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, l: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        interpret=interpret,
        name=name,
    )(x, packed, scales)


def packed_matmul(x, qw: QuantizedLinearWeights, *, bm: int = 128, bn: int = 128,
                  bk: int = 512, interpret=None, name: str = "packed_matmul"):
    """x [M, K] (bf16) @ packed W [K, N] -> f32 [M, N].  ``name`` is the
    kernel's stable name in profiles and op metadata."""
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


def packed_gemv(x, qw: QuantizedLinearWeights, *, bn: int = 256, bk: int = 1024,
                interpret=None):
    """Decode-shape GEMV: x [B, K] with small B (the paper's Section VI-C)."""
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
