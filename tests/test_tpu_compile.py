"""The serving kernels compile for a TPU v5e at real model widths.

Interpret mode (the CPU test backend) accepts block shapes and in-kernel
layouts that Mosaic, the TPU kernel compiler, refuses.  These tests lower
the kernels with ``interpret=False`` against a described (not attached)
v5e and compile them, so a kernel the chip would refuse fails here.
Nothing runs: the tests check that the compiled program holds the kernel
(``tpu_custom_call``), not what it computes.

The topology is described inside a module-scoped fixture, never at import
time: only one process may load the TPU library, and every test worker
imports this file.
"""
from __future__ import annotations

import base64
import collections
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.decode_attention import gqa_decode_attention
from repro.kernels.ops import AUTO_KERNEL_MAX_ROWS
from repro.kernels.packed_matmul import packed_gemv, packed_matmul
from repro.quant.kv_cache import QuantizedKV
from repro.quant.schemes import (QuantizedLinearWeights, effective_group,
                                 get_scheme)
from repro.serve.engine import ServeConfig

GRANITE = get_config("granite-8b")
STARCODER2 = get_config("starcoder2-15b")
QWEN_MOE = get_config("qwen3-moe-30b-a3b")
DECODE_SLOTS = 8
DECODE_CONTEXT = 2048
# granite-8b.batch-decode and starcoder2-15b.batch-decode: 16 decode slots
# and 16-token prefill chunks
CELL_ROWS = ServeConfig().prefill_chunk


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of these tests
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("kv", ["bf16", "int8"])
@pytest.mark.parametrize("heads", [
    (GRANITE.n_heads, GRANITE.n_kv_heads, GRANITE.d_head),
    (QWEN_MOE.n_heads, QWEN_MOE.n_kv_heads, QWEN_MOE.d_head),
    # one shard of qwen3-moe on a 4-way model axis
    (QWEN_MOE.n_heads // 4, QWEN_MOE.n_kv_heads // 4, QWEN_MOE.d_head),
], ids=["granite-8b", "qwen3-moe", "qwen3-moe-tp4-shard"])
def test_decode_attention_compiles(one_chip, heads, kv):
    h, hk, dh = heads
    b, sk = DECODE_SLOTS, DECODE_CONTEXT
    q = ((b, 1, h, dh), jnp.bfloat16)
    lens = ((b,), jnp.int32)
    if kv == "bf16":
        cache = ((b, sk, hk, dh), jnp.bfloat16)
        hlo = _compile(lambda q, k, v, n: gqa_decode_attention(
            q, k, v, n, interpret=False), one_chip, q, cache, cache, lens)
    else:
        packed = ((b, sk, hk, dh // 4), jnp.int32)
        scales = ((b, sk, hk), jnp.float32)

        def fn(q, kp, ks, vp, vs, n):
            return gqa_decode_attention(
                q, QuantizedKV(kp, ks, kv), QuantizedKV(vp, vs, kv), n,
                interpret=False)
        hlo = _compile(fn, one_chip, q, packed, scales, packed, scales, lens)
    assert "tpu_custom_call" in hlo


# granite-8b projection widths (K, N): wq/wo, wk/wv, w_gate/w_up, w_down
GRANITE_WIDTHS = [
    (GRANITE.d_model, GRANITE.n_heads * GRANITE.d_head),
    (GRANITE.d_model, GRANITE.n_kv_heads * GRANITE.d_head),
    (GRANITE.d_model, GRANITE.d_ff),
    (GRANITE.d_ff, GRANITE.d_model),
]


def _packed_weight_shapes(scheme_name: str, k: int, n: int):
    s = get_scheme(scheme_name)
    return (((k // (32 // s.weight_bits), n), jnp.int32),
            ((k // effective_group(s.group_size, k), n), jnp.float32))


def _compile_packed(one_chip, scheme_name, m, k, n):
    scheme = get_scheme(scheme_name)
    kernel = packed_gemv if m <= 8 else packed_matmul

    def fn(x, packed, scales):
        qw = QuantizedLinearWeights(scheme, packed, scales, (k, n))
        return kernel(x, qw, interpret=False)
    return _compile(fn, one_chip, ((m, k), jnp.bfloat16),
                    *_packed_weight_shapes(scheme_name, k, n))


@pytest.mark.parametrize("k,n", GRANITE_WIDTHS,
                         ids=[f"{k}x{n}" for k, n in GRANITE_WIDTHS])
@pytest.mark.parametrize("m", [DECODE_SLOTS, CELL_ROWS, AUTO_KERNEL_MAX_ROWS],
                         ids=["decode", "prefill-chunk", "auto-bound"])
def test_packed_awq_int4_compiles(one_chip, m, k, n):
    """The GEMV (M <= 8), the products of a 16-token prefill chunk (and of
    the benchmark cell's 16 decode slots) and the largest M that
    ``kernel='auto'`` sends to the kernel, at every granite-8b projection
    width, as ``quantized_matmul`` dispatches them."""
    assert "tpu_custom_call" in _compile_packed(one_chip, "awq_int4", m, k, n)


# starcoder2-15b projection widths (K, N): wq/wo, wk/wv, w_up, w_down
STARCODER2_WIDTHS = [
    (STARCODER2.d_model, STARCODER2.n_heads * STARCODER2.d_head),
    (STARCODER2.d_model, STARCODER2.n_kv_heads * STARCODER2.d_head),
    (STARCODER2.d_model, STARCODER2.d_ff),
    (STARCODER2.d_ff, STARCODER2.d_model),
]


def _mosaic_op_counts(hlo: str) -> collections.Counter:
    """Op names in the Mosaic kernel bodies of a compiled program (each
    ``tpu_custom_call`` carries its lowered module as base64 bytecode),
    counted, without the serializer's dialect prefix."""
    from jax.extend.mlir import ir
    counts = collections.Counter()

    def count(op):
        counts[op.name.removeprefix("stable_mosaic.")] += 1
        return ir.WalkResult.ADVANCE
    for body in re.findall(r'"body": ?"([^"]+)"', hlo):
        with ir.Context() as ctx:
            ctx.allow_unregistered_dialects = True
            ir.Module.parse(base64.b64decode(body)).operation.walk(count)
    return counts


SCHEME_WIDTHS = [("mxfp4", k, n)
                 for k, n in GRANITE_WIDTHS + STARCODER2_WIDTHS] \
    + [("fp8", k, n) for k, n in GRANITE_WIDTHS]


@pytest.mark.parametrize("scheme,k,n", SCHEME_WIDTHS,
                         ids=[f"{s}-{k}x{n}" for s, k, n in SCHEME_WIDTHS])
def test_packed_schemes_compile(one_chip, scheme, k, n):
    """The other packed schemes' plans compile at the cells' M (16 decode
    slots, 16-token prefill chunks), mxfp4 also at every starcoder2-15b
    width.  The mxfp4 kernel builds E2M1 values from bits: its lowered
    body holds no ``exp`` / ``exp2``."""
    hlo = _compile_packed(one_chip, scheme, CELL_ROWS, k, n)
    assert "tpu_custom_call" in hlo
    if scheme == "mxfp4":
        ops = _mosaic_op_counts(hlo)
        assert ops["tpu.matmul"] > 0
        assert not {"math.exp", "math.exp2"} & set(ops), ops


def test_packed_awq_int4_lowering_is_pinned(one_chip):
    """The int4 kernel at granite-8b's FFN width and the cell's M lowers to
    a fixed 172 ops: per code plane a shift pair, one int-to-f32 convert,
    the scale multiply, the bf16 round and one MXU product.  The float
    decodes share the kernel's code; a change to them that reaches the
    int branch shows here."""
    k, n = GRANITE.d_model, GRANITE.d_ff
    ops = _mosaic_op_counts(_compile_packed(one_chip, "awq_int4", CELL_ROWS,
                                            k, n))
    assert sum(ops.values()) == 172, ops
    per = {name: ops[name] for name in (
        "arith.shli", "arith.shrsi", "arith.sitofp", "arith.mulf",
        "arith.truncf", "tpu.matmul")}
    assert per == dict.fromkeys(per, 8), per
