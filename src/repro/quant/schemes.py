"""Quantization schemes for mixed-precision LLM inference (paper Table I).

Each scheme describes one MAC datatype combination of the paper and how
weights are quantized into it:

  awq_int4   weight-only INT4 (group-wise, symmetric)  -> INT4 x BF16 + BF16
  w8a8       SmoothQuant-style INT8 weights+acts       -> INT8 x INT8 + INT32
  fp8        E4M3 weights+acts (per-channel scale)     -> FP8 x FP8 + BF16
  mxfp4      MXFP4: FP4 E2M1 + UE8M0 power-of-2 scale  -> FP4 x BF16 + BF16
  bf16       no quantization (attention MACs)          -> BF16 x BF16 + BF16

The FP8 dequant LUT is generated from the core.formats codec, so
kernel-side decode is bit-identical to the XtraMAC Stage-1 mapping
semantics (DAZ, implicit-one restore).  MXFP4 weights are served as the
published format, OCP MX v1.0 E2M1, whose exponent-zero codes 1 and 9 are
the subnormals +-0.5: the serving codec (``FP4_LUT``, ``encode_e2m1`` and
the packed kernel's decode) reads and writes them, while the FPGA MAC
model in ``core/`` keeps its denormals-as-zero rule.  ``quantize_weights``
is the numpy quantizer (offline checkpoint prep, and the oracle);
``quantize_weights_jnp`` is its traceable twin, which builds serving
weights on the device slice by slice; dequantize() has a jnp path used
inside models and kernels.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import formats as F
from .pack import codes_per_word, pack_codes, pack_codes_np, unpack_codes


@dataclasses.dataclass(frozen=True)
class QuantScheme:
    name: str
    weight_format: str         # core.formats name
    act_format: str            # 'bf16' | 'int8' | 'fp8_e4m3'
    acc_format: str            # 'bf16' | 'fp32' | 'int32'
    group_size: int            # scale granularity along K; -1 = per-channel
    weight_bits: int
    scale_pow2: bool = False   # UE8M0-style power-of-two scales (MXFP4)
    pack_in_words: bool = True  # sub-byte/byte codes packed into int32 words

    @property
    def packed(self) -> bool:
        return self.pack_in_words and self.weight_bits <= 8

    @property
    def mac_combo(self) -> str:
        """The XtraMAC datatype combination this scheme executes as."""
        return f"{self.weight_format}x{self.act_format}"


SCHEMES: Dict[str, QuantScheme] = {
    "awq_int4": QuantScheme("awq_int4", "int4", "bf16", "bf16", 128, 4),
    # w8a8 keeps raw int8 [K, N] so the MXU INT8 x INT8 -> INT32 path applies
    "w8a8": QuantScheme("w8a8", "int8", "int8", "int32", -1, 8, pack_in_words=False),
    "fp8": QuantScheme("fp8", "fp8_e4m3", "fp8_e4m3", "bf16", -1, 8),
    "mxfp4": QuantScheme("mxfp4", "fp4_e2m1", "bf16", "bf16", 32, 4, scale_pow2=True),
    "bf16": QuantScheme("bf16", "bf16", "bf16", "bf16", -1, 16, pack_in_words=False),
}


def get_scheme(name: str) -> QuantScheme:
    return SCHEMES[name]


@dataclasses.dataclass
class QuantizedLinearWeights:
    """Packed weights + scales for one linear layer (K in-features x N out)."""
    scheme: QuantScheme
    packed: np.ndarray | jnp.ndarray     # int32 [K/per_word, N] (or bf16 [K,N])
    scales: Optional[np.ndarray | jnp.ndarray]  # f32 [K/G, N] or [1, N] or None
    shape: Tuple[int, int]               # (K, N) logical
    # logical leaf name ("ffn.w_up", ...) when applied from a model tree —
    # the mesh kernel dispatch keys its sharding-spec lookup on it
    name: Optional[str] = None


# ---------------------------------------------------------------------------
# Dequant lookup tables (exact codec values, from core.formats)
# ---------------------------------------------------------------------------
def dequant_lut(fmt_name: str) -> np.ndarray:
    """code -> float32 value table for a <=8-bit float format (DAZ applied)."""
    fmt = F.get_format(fmt_name)
    assert fmt.bits <= 8
    vals = fmt.decode_to_f64(np.arange(1 << fmt.bits))
    return np.nan_to_num(vals, nan=0.0).astype(np.float32)


# OCP MX v1.0 E2M1, codes 0..7 (code 8 + c is the negative of code c)
E2M1_VALUES = (0.0, 0.5, 1.0, 1.5, 2.0, 3.0, 4.0, 6.0)
FP4_LUT = np.array(E2M1_VALUES + tuple(-v for v in E2M1_VALUES), np.float32)
FP8_LUT = dequant_lut("fp8_e4m3")
# the midpoint between neighbouring E2M1 magnitudes, and whether a value
# exactly on it rounds up (to-nearest-even: up where the upper code is even)
_E2M1_MIDPOINTS = ((0.25, False), (0.75, True), (1.25, False), (1.75, True),
                   (2.5, False), (3.5, True), (5.0, False))


def encode_e2m1(x, xp=jnp):
    """f32 values -> OCP E2M1 codes (int32), rounded to nearest even and
    saturating at +-6, +-0.5 kept as the subnormal codes 1 and 9; ``xp`` is
    ``jnp`` or ``numpy``, which give the same codes."""
    mag = xp.abs(x)
    code = sum((mag >= t if up else mag > t).astype(xp.int32)
               for t, up in _E2M1_MIDPOINTS)
    return code | (xp.signbit(x).astype(xp.int32) << 3)


def _int_decode(codes, bits: int):
    """Unsigned codes -> signed two's-complement values (jnp)."""
    half = 1 << (bits - 1)
    return jnp.where(codes >= half, codes - (1 << bits), codes)


def decode_codes(scheme: QuantScheme, codes):
    """jnp: unsigned codes -> float32 format values (pre-scale)."""
    if scheme.weight_format.startswith("int"):
        return _int_decode(codes, scheme.weight_bits).astype(jnp.float32)
    if scheme.weight_format == "fp4_e2m1":
        return jnp.asarray(FP4_LUT)[codes]
    if scheme.weight_format == "fp8_e4m3":
        return jnp.asarray(FP8_LUT)[codes]
    raise ValueError(scheme.weight_format)


# ---------------------------------------------------------------------------
# Quantize (offline / checkpoint preparation; numpy)
# ---------------------------------------------------------------------------
def effective_group(group: int, k: int) -> int:
    """Group size along K (clamped: small test layers use one group)."""
    return k if (group == -1 or group > k) else group


def _group_absmax(w: np.ndarray, group: int) -> np.ndarray:
    k, n = w.shape
    g = effective_group(group, k)
    assert k % g == 0
    return np.abs(w.reshape(k // g, g, n)).max(axis=1)  # [K/G, N]


def quantize_weights(scheme: QuantScheme, w: np.ndarray) -> QuantizedLinearWeights:
    """Quantize a float weight matrix [K, N] into packed codes + scales."""
    w = np.asarray(w, np.float32)
    k, n = w.shape
    if scheme.name == "bf16":
        return QuantizedLinearWeights(scheme, jnp.asarray(w, jnp.bfloat16), None, (k, n))

    g = effective_group(scheme.group_size, k)
    absmax = np.maximum(_group_absmax(w, g), 1e-12)          # [K/G, N]
    # scales multiply by the f32 reciprocal of the code range: XLA turns a
    # division by a constant into exactly that, so ``quantize_weights_jnp``
    # and this quantizer agree bit for bit

    if scheme.weight_format.startswith("int"):
        qmax = (1 << (scheme.weight_bits - 1)) - 1           # symmetric
        scales = absmax * np.float32(1.0 / qmax)
        wg = w.reshape(k // g, g, n)
        q = np.rint(wg / scales[:, None, :]).clip(-qmax - 1, qmax)
        codes = (q.astype(np.int64) & ((1 << scheme.weight_bits) - 1)).reshape(k, n)
    else:
        fmt = F.get_format(scheme.weight_format)
        scales = absmax * np.float32(1.0 / fmt.max_finite)
        if scheme.scale_pow2:  # UE8M0: scale = 2^ceil(log2(absmax / max_finite))
            scales = np.exp2(np.ceil(np.log2(scales)))
        wg = w.reshape(k // g, g, n) / scales[:, None, :]
        if scheme.weight_format == "fp4_e2m1":
            codes = encode_e2m1(wg, np).reshape(k, n)
        else:
            codes = F.quantize_f64(fmt, wg.astype(np.float64)).reshape(k, n)

    if scheme.packed:
        packed = pack_codes_np(codes.astype(np.int64), scheme.weight_bits)
    else:
        packed = codes.astype(np.int8) if scheme.weight_format.startswith("int") \
            else codes.astype(np.uint8)
    return QuantizedLinearWeights(
        scheme, jnp.asarray(packed), jnp.asarray(scales, jnp.float32), (k, n)
    )


def quantize_weights_jnp(scheme: QuantScheme, w):
    """Traceable twin of ``quantize_weights``: float [K, N] -> (packed,
    scales) as jnp arrays, the same codes and scales as the numpy quantizer
    (pinned in tests/test_quant_properties.py).  Runs inside a jit, so a
    serving checkpoint is drawn and quantized on the device, one layer
    slice at a time, without a float copy of the whole stack on the host."""
    w = w.astype(jnp.float32)
    k, n = w.shape
    g = effective_group(scheme.group_size, k)
    wg = w.reshape(k // g, g, n)
    absmax = jnp.maximum(jnp.abs(wg).max(axis=1), 1e-12)      # [K/G, N]
    if scheme.weight_format.startswith("int"):
        qmax = (1 << (scheme.weight_bits - 1)) - 1           # symmetric
        scales = absmax * jnp.float32(1.0 / qmax)
        q = jnp.clip(jnp.round(wg / scales[:, None, :]), -qmax - 1, qmax)
        codes = q.astype(jnp.int32) & ((1 << scheme.weight_bits) - 1)
    else:
        fmt = F.get_format(scheme.weight_format)
        scales = absmax * jnp.float32(1.0 / fmt.max_finite)
        if scheme.scale_pow2:
            scales = jnp.exp2(jnp.ceil(jnp.log2(scales)))
        scaled = jnp.clip(wg / scales[:, None, :], -fmt.max_finite,
                          fmt.max_finite)
        if scheme.weight_format == "fp4_e2m1":
            codes = encode_e2m1(scaled)
        else:
            codes = _encode_minifloat(fmt, scaled)
    codes = codes.reshape(k, n)
    if scheme.packed:
        packed = pack_codes(codes, scheme.weight_bits)
    else:
        packed = codes.astype(jnp.int8) \
            if scheme.weight_format.startswith("int") else codes.astype(jnp.uint8)
    return packed, scales.astype(jnp.float32)


# ---------------------------------------------------------------------------
# Dequantize (jnp; reference path — kernels fuse this into the matmul)
# ---------------------------------------------------------------------------
def dequantize(qw: QuantizedLinearWeights, dtype=jnp.bfloat16):
    """Packed codes + scales -> dense weights [K, N].

    dtype=bf16 is the operand both product paths give the MXU (the jnp
    path materializes it in HBM, the packed kernels build it in VMEM);
    dtype=f32 keeps the unrounded values.
    """
    scheme = qw.scheme
    if scheme.name == "bf16":
        return qw.packed.astype(dtype)
    k, n = qw.shape
    if scheme.packed:
        codes = unpack_codes(qw.packed, scheme.weight_bits)     # [K, N] uint
    else:
        codes = qw.packed.astype(jnp.int32) & ((1 << scheme.weight_bits) - 1)
    vals = decode_codes(scheme, codes)                          # f32 [K, N]
    g = effective_group(scheme.group_size, k)
    vals = vals.reshape(k // g, g, n) * qw.scales[:, None, :]
    return vals.reshape(k, n).astype(dtype)


# ---------------------------------------------------------------------------
# KV-cache quantization (per-head-group; the serving pool's mixed-precision
# side — DESIGN.md §9)
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class KVQuantScheme:
    """8-bit KV-cache quantization with per-(position, head) group scales.

    The scale group is one head's ``d_head`` channel vector — the finest
    granularity that adds no inner-loop rescaling to the decode kernel
    (scores/values for a head consume exactly one K scale and one V scale
    per cached position).  Codes are packed 4-per-int32 word along
    ``d_head`` (quant/pack.py's HBM-word insight applied to the cache) and
    decode through the ``core.formats`` codec semantics: DAZ + implicit-one
    restore, bit-identical to the XtraMAC Stage-1 mapping.
    """
    name: str            # 'int8' | 'fp8'
    fmt_name: str        # backing core.formats codec
    bits: int = 8


KV_SCHEMES: Dict[str, KVQuantScheme] = {
    "int8": KVQuantScheme("int8", "int8"),
    "fp8": KVQuantScheme("fp8", "fp8_e4m3"),
}


def get_kv_scheme(kv_dtype) -> Optional[KVQuantScheme]:
    """Normalize the ``kv_dtype`` knob: None for bf16 storage (including the
    legacy jnp-dtype spelling), a ``KVQuantScheme`` for 'int8' / 'fp8'."""
    if kv_dtype is None or not isinstance(kv_dtype, str):
        return None                    # jnp dtype: plain (unquantized) cache
    if kv_dtype == "bf16":
        return None
    try:
        return KV_SCHEMES[kv_dtype]
    except KeyError as exc:
        raise KeyError(
            f"unknown kv_dtype {kv_dtype!r}; have 'bf16' + {sorted(KV_SCHEMES)}"
        ) from exc


def kv_pack_codes(codes):
    """8-bit codes [..., D] -> int32 words [..., D/4], jnp.

    Planar layout: byte i of word w holds channel ``i * D/4 + w``, so
    unpacking is four shift/mask planes concatenated along the channel
    axis — a lane concatenation inside the decode kernel, where the
    interleaved (channel ``4w + i``) layout would need a lane shuffle."""
    d = codes.shape[-1]
    assert d % 4 == 0, f"trailing dim {d} not divisible by 4 (KV packing)"
    c = (codes.astype(jnp.int32) & 0xFF).reshape(codes.shape[:-1] + (4, d // 4))
    return c[..., 0, :] | (c[..., 1, :] << 8) | (c[..., 2, :] << 16) \
        | (c[..., 3, :] << 24)


def kv_unpack_codes(words):
    """int32 words [..., Dw] -> unsigned 8-bit codes [..., Dw*4], jnp."""
    return jnp.concatenate([(words >> (8 * i)) & 0xFF for i in range(4)],
                           axis=-1)


def _encode_minifloat(fmt, x):
    """jnp RN-even encode of f32 (already clipped to the format's max
    finite) -> uint codes of the minifloat ``fmt`` (E4M3), FTZ on
    underflow — bit-identical to ``core.formats.quantize_f64`` on this
    domain.  NOT the XLA float8 cast: that double-rounds through f16 on
    CPU, flipping round-to-nearest-even ties (e.g. 61.99 -> 64 instead of
    60)."""
    xf = x.astype(jnp.float32)
    sign = jnp.signbit(xf).astype(jnp.int32)
    mag = jnp.abs(xf)
    _, e2 = jnp.frexp(mag)                    # mag = frac * 2^e2, frac [.5,1)
    e_unb = e2 - 1
    # integer mantissa with man_bits fractional bits; the 2^k scaling is
    # exact in f32, so jnp.round is a true RN-even on the real quotient
    m = jnp.round(jnp.ldexp(mag, fmt.man_bits - e_unb)).astype(jnp.int32)
    carry = m >= (1 << (fmt.man_bits + 1))
    m = jnp.where(carry, m >> 1, m)
    e_unb = e_unb + carry
    underflow = (e_unb < fmt.min_unbiased_exp) | (mag == 0)
    sign = sign << (fmt.bits - 1)
    code = sign | ((e_unb + fmt.bias) << fmt.man_bits) \
        | (m & ((1 << fmt.man_bits) - 1))
    return jnp.where(underflow, sign, code)         # FTZ: signed zero


def kv_quantize(scheme: KVQuantScheme, x):
    """jnp (runs inside the jitted prefill/decode steps): quantize-on-write.

    x [..., D] float -> (packed int32 [..., D/4], scales f32 [...]) with one
    symmetric absmax scale per trailing-D group.  int8 is round-to-nearest
    two's complement; fp8 is an RN-even E4M3 encode clipped to the codec's
    max finite, bit-identical to the ``core.formats`` codec.
    """
    xf = x.astype(jnp.float32)
    absmax = jnp.maximum(jnp.max(jnp.abs(xf), axis=-1), 1e-12)
    if scheme.name == "int8":
        scales = absmax / 127.0
        q = jnp.clip(jnp.round(xf / scales[..., None]), -128, 127)
        codes = q.astype(jnp.int32)
    else:                                   # fp8_e4m3
        fmt = F.FP8_E4M3
        scales = absmax / jnp.float32(fmt.max_finite)
        scaled = jnp.clip(xf / scales[..., None],
                          -fmt.max_finite, fmt.max_finite)
        codes = _encode_minifloat(fmt, scaled)
    return kv_pack_codes(codes), scales


def kv_decode_codes(scheme: KVQuantScheme, codes):
    """jnp: unsigned 8-bit codes -> f32 pre-scale values (codec semantics:
    two's complement for int8, DAZ LUT for fp8 — NaN/subnormals read as 0)."""
    if scheme.name == "int8":
        return _int_decode(codes, 8).astype(jnp.float32)
    return jnp.asarray(FP8_LUT)[codes]


def kv_dequantize(scheme: KVQuantScheme, packed, scales, dtype=jnp.bfloat16):
    """jnp: packed words + group scales -> dense KV slab [..., D]."""
    codes = kv_unpack_codes(packed)
    return (kv_decode_codes(scheme, codes) * scales[..., None]).astype(dtype)


def quantize_activations_int8(x):
    """Per-tensor symmetric INT8 activation quant (SmoothQuant-style); jnp."""
    absmax = jnp.maximum(jnp.max(jnp.abs(x.astype(jnp.float32))), 1e-12)
    scale = absmax / 127.0
    codes = jnp.clip(jnp.round(x.astype(jnp.float32) / scale), -128, 127).astype(jnp.int8)
    return codes, scale


def quantize_activations_fp8(x):
    """Per-tensor E4M3 activation quant; returns codes (uint8) + scale; jnp."""
    fmt = F.FP8_E4M3
    absmax = jnp.maximum(jnp.max(jnp.abs(x.astype(jnp.float32))), 1e-12)
    scale = absmax / fmt.max_finite
    scaled = x.astype(jnp.float32) / scale
    # jnp-native E4M3 cast (XLA float8 support), then reinterpret as codes
    codes = scaled.astype(jnp.float8_e4m3fn)
    return codes, scale
