"""Hypothesis differential tests: Pallas kernels vs their jnp oracles.

The kernels are the serving hot path under a mesh (DESIGN.md §14), so this
suite is the property-based pin behind the deterministic sweeps in
``test_kernels.py``:

  * ``packed_matmul`` / ``packed_gemv`` are BIT-exact against
    ``ref.packed_matmul_tiled_ref`` — the oracle that decodes with the
    kernel's own arithmetic path (``decode_codes_arith``) and replays the
    kernel's exact grid — across every packed scheme and adversarial
    shapes: K not a multiple of the default bk, N not a multiple of bn,
    K splitting into several scale groups or exactly one;
  * ``w8a8_matmul`` is BIT-exact against ``ref.w8a8_matmul_ref`` (INT32
    accumulation is associative — no tiling caveat needed);
  * the same runs stay allclose to the plain dequantize-then-dot LUT
    oracle (``ref.packed_matmul_ref``) — the tiled oracle must not drift
    from the mathematical definition.

Everything runs interpret=True on CPU (the conftest platform pin).
"""
import numpy as np
import pytest

hypothesis = pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st  # noqa: E402

import jax.numpy as jnp  # noqa: E402

from repro.kernels import ref  # noqa: E402
from repro.kernels.packed_matmul import (  # noqa: E402
    packed_block_plan, packed_gemv, packed_matmul, packed_shapes_legal,
    w8a8_matmul,
)
from repro.quant.schemes import (  # noqa: E402
    SCHEMES, effective_group, get_scheme, quantize_activations_int8,
    quantize_weights,
)

PACKED_SCHEMES = sorted(n for n, s in SCHEMES.items() if s.packed)


def _draw_k(data, scheme):
    """A legal-but-irregular K: multiple of the packing word and of the
    effective scale group, deliberately NOT a multiple of the default
    bk=512 most of the time, and sometimes a single-group (K < group)
    layer like the smoke configs."""
    per = 32 // scheme.weight_bits
    group = scheme.group_size
    if group == -1:   # per-channel: word-aligned is the only constraint
        return per * data.draw(st.integers(3, 40))
    if data.draw(st.booleans()):
        return group * data.draw(st.integers(1, 5))        # group-aligned
    return per * data.draw(st.integers(1, group // per - 1))  # single group


@pytest.mark.parametrize("scheme_name", PACKED_SCHEMES)
@given(data=st.data())
@settings(max_examples=15, deadline=None)
def test_packed_kernels_bitexact_vs_tiled_ref(scheme_name, data):
    """Kernel == tiled oracle (to ``ref.TILED_REF_ATOL``), for GEMV and
    matmul block plans, on irregular (M, K, N)."""
    scheme = get_scheme(scheme_name)
    rng = np.random.default_rng(data.draw(st.integers(0, 2**31)))
    k = _draw_k(data, scheme)
    n = data.draw(st.integers(1, 24)) * 16      # not always bn-aligned
    m = data.draw(st.sampled_from([1, 2, 3, 5, 8, 9, 16, 33]))
    assert packed_shapes_legal(m, k, n, scheme), (m, k, n)
    qw = quantize_weights(scheme, rng.standard_normal((k, n)).astype(np.float32))
    x = jnp.asarray(rng.standard_normal((m, k)), jnp.bfloat16)

    if m <= 8:   # the dispatch predicate in kernels/ops.py
        got = packed_gemv(x, qw, interpret=True)
        want = ref.packed_matmul_tiled_ref(x, qw, bm=m)
    else:
        got = packed_matmul(x, qw, interpret=True)
        want = ref.packed_matmul_tiled_ref(x, qw)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=0,
        atol=ref.TILED_REF_ATOL * float(np.abs(want).max()))
    # and the tiled oracle has not drifted from the mathematical result
    lut = np.asarray(ref.packed_matmul_ref(x, qw))
    np.testing.assert_allclose(np.asarray(got), lut, rtol=2e-3, atol=1e-3)


@pytest.mark.parametrize("scheme_name", PACKED_SCHEMES)
@given(data=st.data())
@settings(max_examples=10, deadline=None)
def test_block_plan_invariance_bitexact(scheme_name, data):
    """Kernel and oracle agree bitwise for ANY requested block shape —
    both fit the request to the same legal plan (``packed_block_plan``),
    including K blocks that must shrink to a group boundary."""
    scheme = get_scheme(scheme_name)
    rng = np.random.default_rng(data.draw(st.integers(0, 2**31)))
    k = _draw_k(data, scheme)
    n = 32 * data.draw(st.integers(1, 6))
    m = data.draw(st.sampled_from([4, 16]))
    bm = data.draw(st.sampled_from([8, 32, 128]))
    bn = data.draw(st.sampled_from([16, 128, 512]))
    bk = data.draw(st.sampled_from([64, 512, 4096]))
    qw = quantize_weights(scheme, rng.standard_normal((k, n)).astype(np.float32))
    x = jnp.asarray(rng.standard_normal((m, k)), jnp.bfloat16)
    got = packed_matmul(x, qw, bm=bm, bn=bn, bk=bk, interpret=True)
    want = ref.packed_matmul_tiled_ref(x, qw, bm=bm, bn=bn, bk=bk)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # the fitted plan really respected the group/word quantum
    fbm, fbn, fbk = packed_block_plan(m, k, n, scheme, bm=bm, bn=bn, bk=bk)
    g = effective_group(scheme.group_size, k)
    assert m % fbm == 0 and n % fbn == 0 and k % fbk == 0
    assert fbk % min(g, fbk) == 0


@given(data=st.data())
@settings(max_examples=15, deadline=None)
def test_w8a8_bitexact_vs_ref(data):
    """INT8 x INT8 kernel == oracle bitwise on irregular shapes: INT32
    accumulation is exact, so even the tiling is allowed to differ."""
    rng = np.random.default_rng(data.draw(st.integers(0, 2**31)))
    k = 4 * data.draw(st.integers(1, 100))
    n = data.draw(st.integers(1, 40)) * 8
    m = data.draw(st.integers(1, 20))
    qw = quantize_weights(get_scheme("w8a8"),
                          rng.standard_normal((k, n)).astype(np.float32))
    x_codes, x_scale = quantize_activations_int8(
        jnp.asarray(rng.standard_normal((m, k)), jnp.float32))
    got = w8a8_matmul(x_codes, x_scale, qw.packed, qw.scales, interpret=True)
    want = ref.w8a8_matmul_ref(x_codes, x_scale, qw.packed, qw.scales)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=0, atol=0)


@pytest.mark.parametrize("scheme_name", PACKED_SCHEMES)
@given(data=st.data())
@settings(max_examples=10, deadline=None)
def test_sharded_oracle_decomposition_consistent(scheme_name, data):
    """``sharded_packed_matmul_ref`` at tp=1 degenerates to the tiled
    oracle exactly, and the N-sharded decomposition is bitwise equal to
    the unsharded oracle whenever N splits at a block boundary (the K
    loop per output column is untouched by an N split)."""
    scheme = get_scheme(scheme_name)
    rng = np.random.default_rng(data.draw(st.integers(0, 2**31)))
    k = _draw_k(data, scheme)
    tp = data.draw(st.sampled_from([2, 4]))
    n = 128 * tp
    m = data.draw(st.sampled_from([2, 16]))
    qw = quantize_weights(scheme, rng.standard_normal((k, n)).astype(np.float32))
    x = jnp.asarray(rng.standard_normal((m, k)), jnp.bfloat16)
    whole = np.asarray(ref.packed_matmul_tiled_ref(x, qw))
    trivial = np.asarray(ref.sharded_packed_matmul_ref(
        x, qw, tp=1, shard_dim=1))
    np.testing.assert_array_equal(trivial, whole)
    nshard = np.asarray(ref.sharded_packed_matmul_ref(
        x, qw, tp=tp, shard_dim=1))
    np.testing.assert_array_equal(nshard, whole)
