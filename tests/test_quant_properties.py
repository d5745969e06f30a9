"""Hypothesis property tests on quantization + packing invariants."""
import numpy as np
import pytest

hypothesis = pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st  # noqa: E402

from repro.core import formats as F
from repro.quant.pack import codes_per_word, pack_codes_np, unpack_codes
from repro.quant.schemes import (SCHEMES, dequantize, get_scheme,
                                 quantize_weights)


@given(st.integers(2, 8), st.data())
@settings(max_examples=50, deadline=None)
def test_pack_unpack_roundtrip(bits, data):
    if 32 % bits != 0:
        bits = {3: 4, 5: 4, 6: 8, 7: 8}[bits]
    per = codes_per_word(bits)
    k = per * data.draw(st.integers(1, 4))
    n = data.draw(st.integers(1, 8))
    codes = data.draw(st.lists(
        st.integers(0, (1 << bits) - 1), min_size=k * n, max_size=k * n))
    arr = np.array(codes, np.int64).reshape(k, n)
    import jax.numpy as jnp
    packed = pack_codes_np(arr, bits)
    out = np.asarray(unpack_codes(jnp.asarray(packed), bits))
    np.testing.assert_array_equal(out, arr)


@pytest.mark.parametrize("scheme_name", ["awq_int4", "w8a8", "fp8", "mxfp4"])
@given(data=st.data())
@settings(max_examples=10, deadline=None)
def test_quantize_bounded_error(scheme_name, data):
    """|W - dequant(quant(W))| <= scale * ulp-bound per group."""
    scheme = get_scheme(scheme_name)
    rng = np.random.default_rng(data.draw(st.integers(0, 2**31)))
    k = 128 if scheme.group_size == -1 else scheme.group_size
    w = rng.standard_normal((k, 16)).astype(np.float32)
    qw = quantize_weights(scheme, w)
    back = np.asarray(dequantize(qw, dtype=np.float32))
    absmax = np.abs(w).max(axis=0, keepdims=True)
    if scheme.weight_format.startswith("int"):
        qmax = (1 << (scheme.weight_bits - 1)) - 1
        bound = absmax / qmax            # half-ulp rounding, symmetric
    else:
        fmt = F.get_format(scheme.weight_format)
        # worst relative error of the float format + FTZ zone near zero
        bound = absmax * 2.0 ** (-fmt.man_bits)
        if scheme.scale_pow2:
            bound = bound * 2            # UE8M0 scales round UP to pow2
    assert (np.abs(back - w) <= bound + 1e-6).all()


@given(st.sampled_from(["fp4_e2m1", "fp8_e4m3", "fp8_e5m2", "bf16", "fp16"]))
@settings(max_examples=20, deadline=None)
def test_codec_roundtrip_all_patterns(fmt_name):
    """decode -> re-encode is the identity on canonical finite patterns."""
    fmt = F.get_format(fmt_name)
    if fmt.bits > 8:
        return
    bits = F.all_bit_patterns(fmt)
    vals = fmt.decode_to_f64(bits)
    finite = np.isfinite(vals) & (vals != 0.0)
    re = F.quantize_f64(fmt, vals[finite])
    np.testing.assert_array_equal(re, bits[finite])


@given(st.data())
@settings(max_examples=30, deadline=None)
def test_mac_commutes_with_float_math(data):
    """For exactly-representable operands the MAC equals float math."""
    from repro.core.mac import MacConfig, xtramac
    rng = np.random.default_rng(data.draw(st.integers(0, 2**31)))
    cfg = MacConfig.make("int4", "bf16", "bf16", "fp32")
    a = rng.integers(0, 16, 32)
    b = F.quantize_f64(F.BF16, rng.normal(size=32))
    c = F.quantize_f64(F.BF16, rng.normal(size=32))
    out = F.FP32.decode_to_f64(xtramac(cfg, a, b, c))
    a_v = F.INT4.decode_to_f64(a)
    b_v = F.BF16.decode_to_f64(b)
    c_v = F.BF16.decode_to_f64(c)
    # int4*bf16 product is exact in fp32; + bf16 exact in fp32 window
    expect = np.float32(a_v * b_v) + np.float32(c_v)
    np.testing.assert_allclose(out, expect, rtol=1e-6)


@pytest.mark.parametrize("scheme_name", ["awq_int4", "w8a8", "fp8", "mxfp4"])
@given(data=st.data())
@settings(max_examples=10, deadline=None)
def test_quantize_weights_jnp_matches_numpy(scheme_name, data):
    """The traceable quantizer (serving weights built on the device) emits
    the numpy quantizer's codes and scales bit for bit."""
    import jax
    from repro.quant.schemes import quantize_weights_jnp
    scheme = get_scheme(scheme_name)
    rng = np.random.default_rng(data.draw(st.integers(0, 2**31)))
    g = 128 if scheme.group_size == -1 else scheme.group_size
    k = g * data.draw(st.integers(1, 3))
    n = data.draw(st.integers(1, 6)) * 8
    w = (rng.standard_normal((k, n))
         * 10.0 ** data.draw(st.integers(-3, 2))).astype(np.float32)
    want = quantize_weights(scheme, w)
    packed, scales = jax.jit(
        lambda w: quantize_weights_jnp(scheme, w))(w)
    np.testing.assert_array_equal(np.asarray(packed), np.asarray(want.packed))
    np.testing.assert_array_equal(np.asarray(scales), np.asarray(want.scales))
