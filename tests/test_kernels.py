"""Pallas kernel validation (interpret=True on CPU) vs pure-jnp oracles.

Per the harness contract: every kernel sweeps shapes/dtypes and asserts
allclose against the ref.py oracle; the virtual-DSP kernel is BIT-exact
against the int64 packing oracle.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.packing import PAPER_PARALLELISM, solve_lane_plan
from repro.kernels import ref
from repro.kernels.ops import quantized_matmul
from repro.kernels.packed_matmul import (
    packed_block_plan, packed_gemv, packed_matmul, packed_shapes_legal,
    w8a8_matmul,
)
from repro.kernels.xtramac_mac import virtual_dsp_multiply
from repro.quant.schemes import (
    effective_group, get_scheme, quantize_activations_int8, quantize_weights,
)

RNG = np.random.default_rng(7)


def _qw(scheme_name, k, n, scale=1.0):
    w = (RNG.normal(size=(k, n)) * scale).astype(np.float32)
    return w, quantize_weights(get_scheme(scheme_name), w)


# ---------------------------------------------------------------------------
# packed matmul / GEMV: scheme x shape sweep
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("scheme", ["awq_int4", "mxfp4", "fp8"])
@pytest.mark.parametrize("m,k,n", [(1, 256, 128), (4, 512, 256), (128, 1024, 384),
                                   (8, 128, 128)])
def test_packed_matmul_vs_ref(scheme, m, k, n):
    _, qw = _qw(scheme, k, n)
    x = jnp.asarray(RNG.normal(size=(m, k)), jnp.bfloat16)
    got = packed_matmul(x, qw, bm=min(m, 8), bn=128, bk=256, interpret=True)
    want = ref.packed_matmul_ref(x, qw)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-3, atol=1e-3)


@pytest.mark.parametrize("bk,bn", [(128, 128), (256, 384), (1024, 128)])
def test_packed_matmul_block_sweep(bk, bn):
    """Result is block-shape invariant (same math, different tiling)."""
    _, qw = _qw("awq_int4", 1024, 384)
    x = jnp.asarray(RNG.normal(size=(4, 1024)), jnp.bfloat16)
    got = packed_matmul(x, qw, bm=4, bn=bn, bk=bk, interpret=True)
    want = ref.packed_matmul_ref(x, qw)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-3, atol=1e-3)


def test_packed_matmul_accuracy_vs_float():
    """Dequantized INT4 matmul tracks the fp32 matmul within quant error."""
    w, qw = _qw("awq_int4", 2048, 256)
    x = RNG.normal(size=(2, 2048)).astype(np.float32)
    got = np.asarray(packed_matmul(jnp.asarray(x, jnp.bfloat16), qw, interpret=True))
    exact = x @ w
    rel = np.abs(got - exact).max() / np.abs(exact).max()
    # 4-bit group-128 envelope: per-weight err ~ scale/sqrt(12), accumulated
    # over K=2048 as sqrt(K); relative-to-max ~0.15 for Gaussian data
    assert rel < 0.25, rel


def test_w8a8_exact_int32():
    """INT8 kernel accumulation is exact (integer adder path of the paper)."""
    w, qw = _qw("w8a8", 512, 256)
    x = RNG.normal(size=(16, 512)).astype(np.float32)
    x_codes, x_scale = quantize_activations_int8(jnp.asarray(x))
    got = w8a8_matmul(x_codes, x_scale, qw.packed, qw.scales,
                      bm=16, bn=128, bk=256, interpret=True)
    want = ref.w8a8_matmul_ref(x_codes, x_scale, qw.packed, qw.scales)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=0, atol=0)


@pytest.mark.parametrize("scheme", ["awq_int4", "mxfp4", "fp8", "w8a8", "bf16"])
def test_quantized_matmul_dispatch(scheme):
    """Public entry point: kernel path == jnp path for every scheme."""
    _, qw = _qw(scheme, 256, 128)
    x = jnp.asarray(RNG.normal(size=(4, 256)), jnp.bfloat16)
    out_k = quantized_matmul(x, qw, use_kernel=True, interpret=True,
                             out_dtype=jnp.float32)
    out_j = quantized_matmul(x, qw, use_kernel=False, out_dtype=jnp.float32)
    # kernel path accumulates in f32 (fused dequant); the jnp fallback
    # dequantizes INTO bf16 (the paper's Stage-1 mapping) and emits bf16
    # dots — tolerance covers bf16 rounding over K=256
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_j),
                               rtol=2e-2, atol=0.1)


def test_quantized_matmul_batched_shape():
    _, qw = _qw("awq_int4", 256, 128)
    x = jnp.asarray(RNG.normal(size=(2, 3, 256)), jnp.bfloat16)
    out = quantized_matmul(x, qw, use_kernel=False)
    assert out.shape == (2, 3, 128) and out.dtype == jnp.bfloat16
    assert not np.isnan(np.asarray(out, dtype=np.float32)).any()


# ---------------------------------------------------------------------------
# deterministic differential suite: kernel == tiled oracle BITWISE
#
# tests/test_kernel_properties.py carries the hypothesis generalisation of
# these contracts; this section is the always-on deterministic pin (the
# container may not ship hypothesis) over irregular shapes: K not a
# multiple of the default bk, N not a multiple of bn, single-group K.
# ---------------------------------------------------------------------------
def _irregular_shapes(scheme_name):
    """(m, k, n) triples legal for the scheme but hostile to the tiling."""
    s = get_scheme(scheme_name)
    per = 32 // s.weight_bits
    g = s.group_size
    if g == -1:   # per-channel: only word alignment constrains K
        ks = [per * 3, per * 37]
    else:         # group-aligned, plus a single-group K < group
        ks = [g, g * 3, per * max(1, g // per - 1)]
    return [(m, k, n) for k in ks for n in (16, 48, 384) for m in (1, 8, 9, 33)]


@pytest.mark.parametrize("scheme", ["awq_int4", "mxfp4", "fp8"])
def test_packed_kernels_bitexact_vs_tiled_ref(scheme):
    """packed_gemv/packed_matmul == ref.packed_matmul_tiled_ref (to the
    stated ``TILED_REF_ATOL`` contract) on every packed scheme over
    irregular shapes, and allclose to the plain dequantize-then-dot LUT
    oracle."""
    for m, k, n in _irregular_shapes(scheme):
        assert packed_shapes_legal(m, k, n, get_scheme(scheme)), (m, k, n)
        _, qw = _qw(scheme, k, n)
        x = jnp.asarray(RNG.normal(size=(m, k)), jnp.bfloat16)
        if m <= 8:   # the GEMV dispatch predicate in kernels/ops.py
            got = packed_gemv(x, qw, interpret=True)
            want = ref.packed_matmul_tiled_ref(x, qw, bm=m)
        else:
            got = packed_matmul(x, qw, interpret=True)
            want = ref.packed_matmul_tiled_ref(x, qw)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=0,
            atol=ref.TILED_REF_ATOL * float(np.abs(want).max()),
            err_msg=f"{scheme} m={m} k={k} n={n}")
        lut = np.asarray(ref.packed_matmul_ref(x, qw))
        np.testing.assert_allclose(np.asarray(got), lut, rtol=2e-3, atol=1e-3)


@pytest.mark.parametrize("scheme", ["awq_int4", "mxfp4", "fp8"])
@pytest.mark.parametrize("bm,bn,bk", [(8, 16, 64), (32, 128, 512),
                                      (128, 512, 4096)])
def test_packed_block_plan_bitexact(scheme, bm, bn, bk):
    """Any requested block shape fits to the same legal plan in kernel and
    oracle — bitwise equal even when bk must shrink to a group boundary."""
    s = get_scheme(scheme)
    k = s.group_size * 3 if s.group_size > 0 else 4 * 60
    _, qw = _qw(scheme, k, 96)
    x = jnp.asarray(RNG.normal(size=(16, k)), jnp.bfloat16)
    got = packed_matmul(x, qw, bm=bm, bn=bn, bk=bk, interpret=True)
    want = ref.packed_matmul_tiled_ref(x, qw, bm=bm, bn=bn, bk=bk)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    fbm, fbn, fbk = packed_block_plan(16, k, 96, s, bm=bm, bn=bn, bk=bk)
    g = effective_group(s.group_size, k)
    assert 16 % fbm == 0 and 96 % fbn == 0 and k % fbk == 0
    assert fbk % min(g, fbk) == 0


# (k, n) at M = 16 against a scaled-down plan (``_small_plan``): several N
# blocks, several K blocks, several chunks per block, and dims where a
# block or a chunk must fall back to the whole dim
_EDGE_SHAPES = {"awq_int4": [(4096, 256), (1024, 160), (384, 256)],
                "mxfp4": [(4096, 384), (768, 160), (96, 256)],
                "fp8": [(4096, 384), (1020, 160), (64, 256)]}


@pytest.fixture
def _small_plan(monkeypatch):
    """The default plan's sizes cut so that small shapes cross its block
    and chunk edges: 4 KB steps, 64 KB activation blocks, 128-word
    chunks."""
    from repro.kernels import packed_matmul as pm
    monkeypatch.setattr(pm, "STEP_BYTES", 4 << 10)
    monkeypatch.setattr(pm, "X_BLOCK_BYTES", 64 << 10)
    monkeypatch.setattr(pm, "CHUNK_WORDS", 128)
    jax.clear_caches()   # the kernel's jit holds traces of the full plan
    yield pm
    jax.clear_caches()


@pytest.mark.parametrize("case", range(3))
@pytest.mark.parametrize("scheme", ["awq_int4", "mxfp4", "fp8"])
def test_packed_kernel_bitexact_at_plan_edges(_small_plan, scheme, case):
    """At M = 16 (the serving cell's decode slots and prefill chunk) the
    kernel equals the tiled oracle BITWISE across the default plan's block
    and chunk edges, and the LUT oracle to accumulation order."""
    pm = _small_plan
    s = get_scheme(scheme)
    k, n = _EDGE_SHAPES[scheme][case]
    _, qw = _qw(scheme, k, n)
    x = jnp.asarray(RNG.normal(size=(16, k)), jnp.bfloat16)
    got = np.asarray(packed_matmul(x, qw, interpret=True))
    np.testing.assert_array_equal(got, np.asarray(
        ref.packed_matmul_tiled_ref(x, qw)))
    np.testing.assert_allclose(got, np.asarray(ref.packed_matmul_ref(x, qw)),
                               rtol=2e-3, atol=1e-3)
    bm, bn, bk = pm.packed_block_plan(16, k, n, s)
    cw = pm.chunk_words(bk, k, s)
    assert 16 % bm == 0 and n % bn == 0 and k % bk == 0
    assert (bk // (32 // s.weight_bits)) % cw == 0


GRANITE_WIDTHS = [(4096, 4096), (4096, 1024), (4096, 14336), (14336, 4096)]


@pytest.mark.parametrize("k,n", GRANITE_WIDTHS)
@pytest.mark.parametrize("scheme", ["awq_int4", "mxfp4", "fp8"])
def test_default_plan_at_decode_rows(scheme, k, n):
    """At M = 16 the default plan spans all of K (each output block written
    once), streams at least 512 KB of packed words a grid step, and
    decodes whole 8-row tiles of scales per chunk."""
    from repro.kernels.packed_matmul import chunk_words
    s = get_scheme(scheme)
    bm, bn, bk = packed_block_plan(16, k, n, s)
    assert (bm, bk) == (16, k)
    assert bk * bn * s.weight_bits // 8 >= 512 << 10
    cw = chunk_words(bk, k, s)
    per = 32 // s.weight_bits
    g = effective_group(s.group_size, k)
    assert cw % 16 == 0 and (g >= bk or (cw * per // g) % 8 == 0)


@pytest.mark.parametrize("m,k,n", [(1, 4, 8), (20, 400, 312), (7, 52, 8)])
def test_w8a8_bitexact_irregular(m, k, n):
    """INT32 accumulation is associative, so the INT8 kernel stays bitwise
    equal to its oracle even on shapes the tiling has to pad around."""
    _, qw = _qw("w8a8", k, n)
    x_codes, x_scale = quantize_activations_int8(
        jnp.asarray(RNG.normal(size=(m, k)), jnp.float32))
    got = w8a8_matmul(x_codes, x_scale, qw.packed, qw.scales, interpret=True)
    want = ref.w8a8_matmul_ref(x_codes, x_scale, qw.packed, qw.scales)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=0, atol=0)


@pytest.mark.parametrize("scheme", ["awq_int4", "mxfp4", "fp8"])
@pytest.mark.parametrize("tp", [2, 4])
def test_sharded_oracle_decomposition(scheme, tp):
    """sharded_packed_matmul_ref degenerates to the tiled oracle at tp=1
    and its N-sharded decomposition is bitwise equal to the whole."""
    s = get_scheme(scheme)
    k = s.group_size * 2 if s.group_size > 0 else 4 * 32
    _, qw = _qw(scheme, k, 128 * tp)
    x = jnp.asarray(RNG.normal(size=(2, k)), jnp.bfloat16)
    whole = np.asarray(ref.packed_matmul_tiled_ref(x, qw))
    trivial = np.asarray(ref.sharded_packed_matmul_ref(x, qw, tp=1, shard_dim=1))
    np.testing.assert_array_equal(trivial, whole)
    nshard = np.asarray(ref.sharded_packed_matmul_ref(x, qw, tp=tp, shard_dim=1))
    np.testing.assert_array_equal(nshard, whole)


# ---------------------------------------------------------------------------
# virtual-DSP kernel: bit-exact vs the int64 packing oracle
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("pair", sorted(PAPER_PARALLELISM))
def test_virtual_dsp_bitexact(pair):
    plan = solve_lane_plan(*pair, max_parallelism=4)
    n_a, n_b = len(plan.offsets_a), len(plan.offsets_b)
    t = 2048
    a = RNG.integers(0, plan.w_a and (1 << plan.w_a), size=(t, n_a), dtype=np.int64)
    b = RNG.integers(0, 1 << plan.w_b, size=(t, n_b), dtype=np.int64)
    got = np.asarray(virtual_dsp_multiply(a, b, plan, bt=512, interpret=True))
    want = ref.virtual_dsp_ref(plan, a, b)
    np.testing.assert_array_equal(got, want)


def test_virtual_dsp_max_magnitudes():
    """Boundary case: all lanes at max magnitude (full 45-bit product)."""
    plan = solve_lane_plan("bf16", "bf16", max_parallelism=4)
    n_a, n_b = len(plan.offsets_a), len(plan.offsets_b)
    a = np.full((256, n_a), (1 << plan.w_a) - 1, dtype=np.int64)
    b = np.full((256, n_b), (1 << plan.w_b) - 1, dtype=np.int64)
    got = np.asarray(virtual_dsp_multiply(a, b, plan, bt=256, interpret=True))
    want = ref.virtual_dsp_ref(plan, a, b)
    np.testing.assert_array_equal(got, want)


def test_interpret_mode_follows_platform(monkeypatch):
    """Interpret mode is decided in one place: on for a backend that is not
    a TPU, never on a TPU — an explicit request for it there is refused."""
    from repro.kernels import resolve_interpret
    assert resolve_interpret() is True          # the CPU test backend
    assert resolve_interpret(False) is False    # cross-compiling for a TPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert resolve_interpret() is False
    assert resolve_interpret(False) is False
    with pytest.raises(ValueError, match="never runs on a TPU"):
        resolve_interpret(True)


# OCP MX v1.0 E2M1, codes 0..15: the subnormals 0.5 (code 1) and -0.5
# (code 9) are values, not zeros, and code 8 is -0
OCP_E2M1 = (0.0, 0.5, 1.0, 1.5, 2.0, 3.0, 4.0, 6.0,
            -0.0, -0.5, -1.0, -1.5, -2.0, -3.0, -4.0, -6.0)


@pytest.mark.parametrize("code", range(16))
def test_mxfp4_codes_read_and_write_as_ocp_e2m1(code):
    """The serving codec of each E2M1 code: the packed kernel's decode, the
    jnp LUT behind ``decode_codes`` / ``dequantize``, and the packed kernel
    run on a weight holding the code all read the OCP value; both weight
    quantizers write the code for that value times the group's scale."""
    from repro.kernels.packed_matmul import _decode_fp4_e2m1
    from repro.quant.schemes import decode_codes, quantize_weights_jnp
    scheme = get_scheme("mxfp4")
    want = np.float32(OCP_E2M1[code])
    c = jnp.asarray([code], jnp.int32)
    for got in (_decode_fp4_e2m1(c), decode_codes(scheme, c)):
        got = np.asarray(got)[0]
        assert got == want and np.signbit(got) == np.signbit(want)
    # row 0 holds the code's value times 4; row 1 the largest magnitude,
    # 6 * 4, which sets the first group's power-of-two scale to 4
    w = np.zeros((256, 128), np.float32)
    w[0], w[1] = 4.0 * want, 24.0
    qw = quantize_weights(scheme, w)
    assert float(qw.scales[0, 0]) == 4.0
    packed_jnp, _ = quantize_weights_jnp(scheme, jnp.asarray(w))
    np.testing.assert_array_equal(np.asarray(packed_jnp),
                                  np.asarray(qw.packed))
    assert int(np.asarray(qw.packed)[0, 0]) & 15 == code
    x = jnp.zeros((8, 256), jnp.bfloat16).at[:, 0].set(1.0)
    out = packed_gemv(x, qw, interpret=True)
    np.testing.assert_array_equal(np.asarray(out)[:, 0],
                                  np.full(8, 4.0 * want, np.float32))


@pytest.mark.parametrize("code", range(16))
def test_mxfp4_planes_read_the_published_table(code):
    """Against the OCP table (``FP4_LUT``), not the kernel's arithmetic:
    ``_decode_fp4_e2m1`` reads the code's f32 bits, and every code plane
    ``dequant_planes`` builds from words holding it (other codes in the
    other nibbles), scaled by each power of two 2^-20..2^20, is
    bf16(FP4_LUT[code] * scale) bit for bit: compared as int16, so -0
    counts."""
    from repro.kernels.packed_matmul import _decode_fp4_e2m1, dequant_planes
    from repro.quant.schemes import FP4_LUT
    got = np.asarray(_decode_fp4_e2m1(jnp.asarray([code], jnp.int32)))
    assert got.view(np.int32)[0] == FP4_LUT.view(np.int32)[code]
    # word w holds ``code`` in nibble w and (code + w + i + 1) % 16 in the
    # other nibbles i, so each plane reads the code once among neighbours
    w, i = np.arange(8)[:, None], np.arange(8)[None, :]
    nib = np.where(w == i, code, (code + w + i + 1) % 16).astype(np.uint32)
    words = (nib << (4 * i).astype(np.uint32)).sum(1, dtype=np.uint32)
    scales = np.exp2(np.arange(-20, 21)).astype(np.float32)[None, :]
    words = jnp.broadcast_to(jnp.asarray(words.view(np.int32))[:, None],
                             (8, scales.shape[1]))
    planes = dequant_planes(words, jnp.asarray(scales), get_scheme("mxfp4"))
    for i, plane in enumerate(planes):
        want = jnp.asarray(FP4_LUT[nib[:, i]][:, None] * scales) \
            .astype(jnp.bfloat16)
        np.testing.assert_array_equal(np.asarray(plane).view(np.int16),
                                      np.asarray(want).view(np.int16))


def test_patched_e2m1_decode_reaches_the_kernel(monkeypatch):
    """The packed kernel reads E2M1 through ``_decode_fp4_e2m1``: with it
    patched to read codes 1 and 9 as zero (the FPGA MAC model's DAZ rule,
    the benchmark's flushed-decode fault), ``packed_gemv`` on a weight
    holding code 1 returns 0, not 0.5 times the scale."""
    from repro.kernels import packed_matmul as pm
    orig = pm._decode_fp4_e2m1
    monkeypatch.setattr(pm, "_decode_fp4_e2m1", lambda codes: jnp.where(
        (codes & 7) == 1, 0.0, orig(codes)))
    # row 0 holds code 1 (0.5 x the group's scale 4); row 1 sets the scale
    w = np.zeros((256, 128), np.float32)
    w[0], w[1] = 2.0, 24.0
    qw = quantize_weights(get_scheme("mxfp4"), w)
    assert int(np.asarray(qw.packed)[0, 0]) & 15 == 1
    x = jnp.zeros((8, 256), jnp.bfloat16).at[:, 0].set(1.0)
    pm._packed_matmul_impl.clear_cache()   # trace the patched decode
    try:
        out = np.asarray(packed_gemv(x, qw, interpret=True))
    finally:
        pm._packed_matmul_impl.clear_cache()
    np.testing.assert_array_equal(out[:, 0], np.zeros(8, np.float32))
