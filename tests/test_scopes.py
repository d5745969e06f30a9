"""Named scopes and kernel names in the compiled serving programs.

The benchmark attributes device time to model parts by the ``op_name``
path each HLO instruction carries (the profiler's ``tf_op``), so these
tests lower the step programs at the smoke configuration with quantized
weights and read that metadata back from the optimized HLO: every weight
product and dequantize op lies under ``qmatmul``, the KV slab updates under
``kv_write``, scores and softmax under ``attn_core``, the head under
``lm_head`` and the sampler under ``sample`` — on the jnp path and on the
Pallas path (interpret mode here), where each kernel also carries its
stable ``name``.
"""
import re

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config
from repro.kernels.ops import declare_execution
from repro.models import transformer as T
from repro.models.common import QuantMaker
from repro.serve import ServeConfig, ServingEngine

SCOPES = ("qmatmul", "attn_core", "kv_write", "lm_head", "sample", "attn",
          "ffn", "moe")
_INSTR = re.compile(r"\s*(?:ROOT )?%\S+ = (.+?) ([a-z][\w-]*)\(")
_INT = re.compile(r"^[su](8|16|32|64)\[")


@pytest.fixture(scope="module")
def engine():
    cfg = get_config("granite-8b", smoke=True)
    assert cfg.scheme_proj == cfg.scheme_ffn == "awq_int4"
    params = T.build_params(cfg, QuantMaker(jax.random.PRNGKey(0)))
    return ServingEngine(cfg, params, ServeConfig(
        max_len=48, n_slots=4, prefill_chunk=8, max_burst=8))


def compiled_ops(engine, program, kernel):
    """[(opcode, result type, scopes on its op_name path)] of every
    instruction of the optimized HLO, fused computations included."""
    declare_execution(kernel=kernel)
    pool = engine.new_pool()

    def fresh(fn, **kw):
        # a new function object: JAX's trace cache, keyed by the function,
        # would otherwise hand back the other kernel mode's trace
        return jax.jit(lambda *a: fn(*a), **kw)

    n, S = pool.n_slots, jax.ShapeDtypeStruct
    cache = jax.tree_util.tree_map(lambda a: S(a.shape, a.dtype), pool.cache)
    row = S((n,), jnp.int32)
    if program == "decode_burst":
        lowered = fresh(engine._decode_burst_fn).lower(
            engine.params, cache, row, row, S((n,), jnp.bool_), row,
            S((4, n, 2), jnp.uint32), S((n,), jnp.float32), row,
            jnp.int32(pool.max_len))
    elif program == "decode_slots":
        lowered = fresh(engine._decode_slots_fn).lower(
            engine.params, S((n, 1), jnp.int32), cache, row,
            S((n, 2), jnp.uint32), S((n,), jnp.float32))
    else:
        c = engine.scfg.prefill_chunk
        lowered = fresh(engine._prefill_chunk_fn, static_argnums=(5,)).lower(
            engine.params, S((1, c), jnp.int32), cache, jnp.int32(0),
            jnp.int32(0), True)
    ops = []
    for line in lowered.compile().as_text().splitlines():
        m, name = _INSTR.match(line), re.search(r'op_name="([^"]*)"', line)
        if m and name:
            path = name.group(1).split("/")
            ops.append((m.group(2), m.group(1), name.group(1),
                        {s for s in SCOPES if s in path}))
    return ops, tuple(pool.cache[0].shape[2:])   # [L, slots, *slab]


PROGRAMS = ("decode_burst", "decode_slots", "prefill_chunk")


@pytest.mark.parametrize("kernel", ["jnp", "pallas"])
@pytest.mark.parametrize("program", PROGRAMS)
def test_step_programs_carry_the_scopes(engine, program, kernel):
    ops, slab = compiled_ops(engine, program, kernel)
    dots = [scopes for op, _, _, scopes in ops if op == "dot"]
    # every product: a quantized weight's, attention's, or the head's
    assert all(s & {"qmatmul", "attn_core", "lm_head"} for s in dots), dots
    # the seven quantized linears of a layer (q, k, v, o, gate, up, down)
    assert sum("qmatmul" in s for s in dots) >= 7
    assert sum("lm_head" in s for s in dots) >= 1
    # dequantize: integer bit twiddling happens only in the weight unpack
    # (and in the sampler's PRNG)
    bits = [(t, s) for op, t, _, s in ops
            if op in ("and", "shift-right-logical", "shift-right-arithmetic",
                      "shift-left") and _INT.match(t)]
    assert bits and all(s & {"qmatmul", "sample"} for _, s in bits), bits
    assert any("qmatmul" in s for _, s in bits)
    # every update of a layer's KV slab ([rows, capacity, heads, d_head])
    # is a kv_write (the interpret-mode kernel's copies of its refs aside)
    dims = ",".join(str(d) for d in slab)
    writes = [s for op, t, n, s in ops
              if op in ("scatter", "dynamic-update-slice")
              and re.match(rf"\w+\[\d+,{dims}\]", t)
              and "/decode_attention/" not in n]
    assert len(writes) >= 2 and all("kv_write" in s for s in writes), writes
    # scores and softmax (the einsum path leaves its two products as dots)
    exps = [s for op, _, _, s in ops if op == "exponential"]
    assert any("attn_core" in s for s in exps)
    assert all(s & {"attn_core", "ffn", "sample"} for s in exps), exps
    if kernel == "jnp" and program != "prefill_chunk":
        assert sum("attn_core" in s for s in dots) == 2
    if program != "prefill_chunk":
        assert any("sample" in s for _, _, _, s in ops)
    if kernel == "pallas":
        names = [n for _, _, n, _ in ops]
        assert any(re.search(r"/qmatmul/.*packed_gemv/", n) for n in names)
        if program != "prefill_chunk":
            assert any(re.search(r"/attn_core/decode_attention/", n)
                       for n in names)


@pytest.fixture(scope="module")
def biased_engine():
    """starcoder2's block: LayerNorm with beta, a bias on every linear."""
    cfg = get_config("starcoder2-15b", smoke=True)
    assert cfg.use_bias and cfg.norm == "layer"
    params = T.build_params(cfg, QuantMaker(jax.random.PRNGKey(0)))
    return ServingEngine(cfg, params, ServeConfig(
        max_len=48, n_slots=4, prefill_chunk=8, max_burst=8))


@pytest.mark.parametrize("kernel", ["jnp", "pallas"])
def test_bias_and_norm_ops_land_in_their_blocks(biased_engine, kernel):
    """Each bias add and each norm carries its own scope (``bias``,
    ``norm``) inside ``attn`` / ``ffn`` (the final norm inside
    ``lm_head``) and outside ``qmatmul``, so the benchmark's reduction
    credits them to the block's own time and ``qmatmul`` stays the weight
    products'."""
    ops, _ = compiled_ops(biased_engine, "decode_burst", kernel)
    for part, blocks in (("bias", {"attn", "ffn"}),
                         ("norm", {"attn", "ffn", "lm_head"})):
        found = [s for _, _, n, s in ops if f"/{part}/" in n]
        assert found and all(s & blocks and "qmatmul" not in s
                             for s in found), (part, found)
        assert any("attn" in s for s in found)
        assert any("ffn" in s for s in found)
    adds = [s for op, _, n, s in ops if op == "add" and "/bias/" in n]
    assert any("attn" in s for s in adds) and any("ffn" in s for s in adds)


def _kernel_call(name):
    """(function, args) calling the Pallas kernel ``name`` in interpret
    mode at a small legal shape."""
    import numpy as np
    from repro.core.packing import solve_lane_plan
    from repro.kernels.decode_attention import gqa_decode_attention
    from repro.kernels.packed_matmul import (packed_gemv, packed_matmul,
                                             w8a8_matmul)
    from repro.kernels.xtramac_mac import virtual_dsp_multiply
    from repro.quant.schemes import get_scheme, quantize_weights

    w = np.random.default_rng(0).normal(size=(256, 128)).astype(np.float32)
    qw = quantize_weights(get_scheme("awq_int4"), w)
    if name in ("packed_matmul", "packed_gemv"):
        fn = packed_matmul if name == "packed_matmul" else packed_gemv
        m = 16 if name == "packed_matmul" else 4
        return (lambda x: fn(x, qw, interpret=True),
                (jnp.ones((m, 256), jnp.bfloat16),))
    if name == "w8a8_matmul":
        return (lambda a, b: w8a8_matmul(a, jnp.float32(1), b,
                                         jnp.ones((1, 128)), interpret=True),
                (jnp.ones((16, 256), jnp.int8), jnp.ones((256, 128), jnp.int8)))
    if name == "decode_attention":
        q = jnp.ones((2, 1, 4, 32), jnp.bfloat16)
        kv = jnp.ones((2, 64, 2, 32), jnp.bfloat16)
        return (lambda q, k, v: gqa_decode_attention(
            q, k, v, jnp.array([5, 9], jnp.int32), interpret=True),
            (q, kv, kv))
    plan = solve_lane_plan("int4", "int4", max_parallelism=4)
    a = jnp.ones((256, len(plan.offsets_a)), jnp.int32)
    b = jnp.ones((256, len(plan.offsets_b)), jnp.int32)
    return (lambda a, b: virtual_dsp_multiply(a, b, plan, bt=256,
                                              interpret=True), (a, b))


@pytest.mark.parametrize("name", ["packed_gemv", "packed_matmul",
                                  "w8a8_matmul", "decode_attention",
                                  "xtramac_mac"])
def test_kernels_carry_their_names(name):
    """Each Pallas kernel's stable ``name`` lands on its ops."""
    fn, args = _kernel_call(name)
    txt = jax.jit(fn).lower(*args).compile().as_text()
    paths = re.findall(r'op_name="([^"]*)"', txt)
    assert any(f"/{name}/" in p for p in paths), sorted(set(paths))[:5]


# ---------------------------------------------------------------------------
# kernel='auto' on one device: the packed kernel for quantized products on
# a backend that compiles Pallas, the jnp path everywhere else
# ---------------------------------------------------------------------------
@pytest.fixture
def native_pallas(monkeypatch):
    """The dispatch sees a backend that compiles Pallas natively (a TPU);
    the kernels themselves still run in interpret mode on the CPU."""
    from repro.kernels import ops
    monkeypatch.setattr(ops, "pallas_native", lambda: True)
    return ops


@pytest.mark.parametrize("program", PROGRAMS)
def test_auto_on_tpu_runs_packed_kernel(engine, native_pallas, program):
    ops, _ = compiled_ops(engine, program, "auto")
    paths = native_pallas.site_paths()
    assert set(paths) >= {"attn.wq", "attn.wk", "attn.wv", "attn.wo",
                          "ffn.w_gate", "ffn.w_up", "ffn.w_down"}, paths
    assert set(paths.values()) == {"pallas"}, paths
    names = [n for _, _, n, _ in ops]
    assert any(re.search(r"/qmatmul/.*packed_(gemv|matmul)/", n)
               for n in names)
    # the dequantize graph is gone: no integer unpack outside the kernel
    assert not [n for op, t, n, s in ops if "qmatmul" in s
                and op in ("and", "shift-right-logical", "shift-left")
                and _INT.match(t) and "/packed_" not in n]
    # decode attention keeps its einsum path on one chip
    assert not any("/decode_attention/" in n for n in names)
    if program != "prefill_chunk":
        dots = [s for op, _, _, s in ops if op == "dot"]
        assert sum("attn_core" in s for s in dots) == 2


def test_auto_on_cpu_stays_jnp(engine):
    from repro.kernels.ops import site_paths
    ops, _ = compiled_ops(engine, "decode_burst", "auto")
    paths = site_paths()
    assert paths and set(paths.values()) == {"jnp"}, paths
    assert not any("/packed_" in n for _, _, n, _ in ops)


def _traced_path(x_rows, scheme="awq_int4", k=256, n=128):
    """Trace one product of ``x_rows`` rows and return its site's path."""
    import dataclasses
    import numpy as np
    from repro.kernels.ops import quantized_matmul, site_paths
    from repro.quant.schemes import get_scheme, quantize_weights
    w = np.random.default_rng(0).normal(size=(k, n)).astype(np.float32)
    qw = dataclasses.replace(quantize_weights(get_scheme(scheme), w),
                             name="site")
    jax.jit(lambda x: quantized_matmul(x, qw)).lower(
        jax.ShapeDtypeStruct((x_rows, k), jnp.bfloat16))
    return site_paths()["site"]


def test_auto_row_bound(native_pallas):
    """'auto' takes the kernel up to ``AUTO_KERNEL_MAX_ROWS`` rows and the
    jnp path above them; w8a8 (not packed) stays jnp."""
    bound = native_pallas.AUTO_KERNEL_MAX_ROWS
    assert _traced_path(16) == "pallas"
    assert _traced_path(bound) == "pallas"
    assert _traced_path(bound + 1) == "jnp"
    assert _traced_path(16, scheme="w8a8") == "jnp"


@pytest.mark.parametrize("native", [False, True])
def test_pins_unchanged(monkeypatch, native):
    """'jnp' and 'pallas' pin every quantized product, whatever the
    backend."""
    from repro.kernels import ops
    monkeypatch.setattr(ops, "pallas_native", lambda: native)
    for pin in ("jnp", "pallas"):
        ops.declare_execution(kernel=pin)
        assert _traced_path(16) == pin
        assert _traced_path(1024) == pin
