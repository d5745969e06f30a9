"""Shared model-layer machinery.

Central idea: every architecture's parameter tree is built by ONE structure
walker driven by a ``Maker``.  Four makers produce, from the same walk:
  * InitMaker      real bf16 dense parameters (training / smoke tests)
  * QuantMaker     real quantized parameters (packed codes + scales),
                   drawn and quantized on the device one layer slice at a
                   time — mixed-precision serving
  * AbstractMaker  jax.ShapeDtypeStruct trees (dry-run: zero allocation)
  * PspecMaker     jax.sharding.PartitionSpec trees (pjit annotations)
so parameter structure, quantization plan, and sharding can never drift.

Quantized linears are ``QLinear`` pytree nodes: children = (packed, scales),
static aux = (scheme name, logical shape) — jit/scan/pjit-safe.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.kernels.ops import quantized_matmul
from repro.quant.schemes import (
    QuantScheme, QuantizedLinearWeights, get_scheme, quantize_weights_jnp,
)


@jax.tree_util.register_pytree_node_class
class QLinear:
    """Quantized linear weights as a pytree node (packed codes + scales).

    ``name`` is the leaf's logical name from the Maker walk ("attn.wq",
    "ffn.w_down", ...) — static aux, set identically by every Maker (so
    parameter and spec trees keep matching structures).  It is how the
    mesh kernel dispatch (kernels/ops.py) finds the leaf's sharding spec
    in ``partitioning.serve_weight_kernel_specs`` at apply time."""

    def __init__(self, packed, scales, scheme_name: str,
                 shape: Tuple[int, int], name: Optional[str] = None):
        self.packed = packed
        self.scales = scales
        self.scheme_name = scheme_name
        self.shape = tuple(shape)
        self.name = name

    def tree_flatten(self):
        return (self.packed, self.scales), (self.scheme_name, self.shape,
                                            self.name)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children[0], children[1], *aux)

    def __repr__(self):
        return f"QLinear({self.scheme_name}, {self.shape}, {self.name})"


def set_use_kernel(flag: bool) -> None:
    """Deprecated shim: kernel selection is part of the execution policy
    (``kernels.ops.declare_execution`` / ``PrecisionPolicy.kernel``)."""
    from repro.kernels.ops import declare_execution
    declare_execution(kernel="pallas" if flag else "jnp")


# ---------------------------------------------------------------------------
# Activation sharding constraints (set by launch/steps.py before tracing).
# Without these GSPMD may propagate FSDP *storage* shardings into the
# computation (e.g. batch replicated, d_model sharded) — constraining the
# per-layer activation layout pins DP on batch and lets the compiler insert
# the FSDP all-gathers on weights instead.
# ---------------------------------------------------------------------------
_ACT_SHARDINGS = {"rules": None}


def set_activation_shardings(rules) -> None:
    """rules: dict kind -> NamedSharding (e.g. {'btd': ..., 'logits': ...})
    or None to disable."""
    _ACT_SHARDINGS["rules"] = rules


def shard_act(x, kind: str):
    rules = _ACT_SHARDINGS["rules"]
    if rules is None or kind not in rules or rules[kind] is None:
        return x
    s = rules[kind]
    if x.ndim != len(s.spec):
        return x
    # strip axes whose size doesn't divide the dim (e.g. 4 KV heads on a
    # 16-way model axis stay replicated)
    mesh = s.mesh
    parts = []
    changed = False
    for dim, ax in enumerate(s.spec):
        if ax is None:
            parts.append(None)
            continue
        axes = ax if isinstance(ax, tuple) else (ax,)
        size = 1
        for a in axes:
            size *= mesh.shape[a]
        if x.shape[dim] % size == 0 and x.shape[dim] >= size:
            parts.append(ax)
        else:
            parts.append(None)
            changed = True
    if changed:
        from jax.sharding import NamedSharding, PartitionSpec
        s = NamedSharding(mesh, PartitionSpec(*parts))
    return jax.lax.with_sharding_constraint(x, s)


def apply_linear(leaf, x, out_dtype=jnp.bfloat16, bias=None):
    """x [..., K] @ linear leaf (+ ``bias`` [N]) -> [..., N]; dispatches
    dense vs quantized.

    Dots are bf16-storage: the TPU MXU accumulates in f32 natively, and
    requesting an f32 result dtype makes the CPU backend (the dry-run
    instrument) materialize f32 copies of the weights per use.  A bias is
    added to the product's f32 result under the ``bias`` scope, outside
    ``qmatmul``, and the sum is rounded once to ``out_dtype``.
    """
    if bias is not None:
        y = apply_linear(leaf, x, jnp.float32)
        with jax.named_scope("bias"):
            return (y + bias).astype(out_dtype)
    if isinstance(leaf, QLinear):
        qw = QuantizedLinearWeights(
            get_scheme(leaf.scheme_name), leaf.packed, leaf.scales,
            leaf.shape, name=leaf.name
        )
        # use_kernel=None: dispatch on the active execution policy
        # (kernels.ops.declare_execution) — shard_map'd under a declared
        # mesh, falling back per site
        return quantized_matmul(x, qw, out_dtype=out_dtype)
    return jnp.dot(x.astype(leaf.dtype), leaf).astype(out_dtype)


# ---------------------------------------------------------------------------
# Makers
# ---------------------------------------------------------------------------
class Maker:
    """Builds parameter leaves.  ``stack`` = leading layer-stack dims ()/(L,)."""

    def dense(self, name: str, stack: Tuple[int, ...], k: int, n: int,
              scheme: Optional[str] = None):
        raise NotImplementedError

    def table(self, name: str, stack: Tuple[int, ...], rows: int, cols: int,
              scale: float = 0.02):
        raise NotImplementedError

    def norm(self, name: str, stack: Tuple[int, ...], dim: int):
        raise NotImplementedError

    def vector(self, name: str, stack: Tuple[int, ...], dim: int,
               init: float = 0.0):
        raise NotImplementedError

    def bias(self, name: str, stack: Tuple[int, ...], n: int):
        """The f32 [*stack, n] bias of linear ``name`` (its output axis)."""
        raise NotImplementedError


class InitMaker(Maker):
    """Real dense bf16 parameters (ignores quantization schemes)."""

    def __init__(self, key, dtype=jnp.bfloat16):
        self._key = key
        self.dtype = dtype

    def _next(self):
        self._key, sub = jax.random.split(self._key)
        return sub

    def dense(self, name, stack, k, n, scheme=None):
        return _draw_dense(self._next(), stack + (k, n), self.dtype)

    def table(self, name, stack, rows, cols, scale=0.02):
        return _draw_table(self._next(), stack + (rows, cols), scale,
                           self.dtype)

    def norm(self, name, stack, dim):
        return jnp.ones(stack + (dim,), jnp.float32)

    def vector(self, name, stack, dim, init=0.0):
        return jnp.full(stack + (dim,), init, jnp.float32)

    def bias(self, name, stack, n):
        return _draw_bias(self._next(), stack + (n,))


class QuantMaker(InitMaker):
    """Real quantized parameters, drawn and quantized on the device.

    Each quantized leaf is built one slice of its leading stack dim (one
    layer, with all its experts) at a time: a jitted step draws the slice's
    float weights from ``fold_in(leaf key, slice)``, quantizes them with
    ``quantize_weights_jnp`` (bit-identical to the numpy quantizer) and
    writes the codes and scales into the donated stack in place.  Peak
    memory is the packed stack plus one float slice, whatever the depth.

    ``plan``: optional per-leaf scheme overrides, keyed by the leaf's
    logical name ("attn.wo", "ffn.w_down", "moe.w_up", ...) — the same
    names the partitioning rules use.  A plan entry wins over the config's
    ``scheme=``; 'bf16' (or None) keeps the leaf dense.  Sharding specs for
    a plan-built checkpoint must be built with the same plan
    (``partitioning.param_specs(..., plan=...)``) or the trees diverge.

    ``specs``: optional spec maker (``partitioning.spec_maker`` with
    ``train=False`` and the same plan) — every leaf, and every slice of
    it, is then created directly in its mesh shards.  Without it leaves
    land on the default device.
    """

    def __init__(self, key, plan: Optional[Dict[str, str]] = None,
                 dtype=jnp.bfloat16, specs=None):
        super().__init__(key, dtype)
        self.plan = dict(plan or {})
        self.specs = specs

    def _placement(self, kind, *args):
        """NamedShardings of the leaf the spec maker's ``kind`` method
        describes, or None without a spec maker (the default device)."""
        if self.specs is None:
            return None
        from jax.sharding import NamedSharding
        return jax.tree_util.tree_map(
            lambda p: NamedSharding(self.specs.mesh, p),
            getattr(self.specs, kind)(*args),
            is_leaf=lambda x: isinstance(x, P))

    def dense(self, name, stack, k, n, scheme=None):
        scheme = self.plan.get(name, scheme)
        scheme = scheme if scheme is not None else "bf16"
        sh = self._placement("dense", name, stack, k, n, scheme)
        key = self._next()
        if scheme == "bf16":
            return jax.jit(lambda: _draw_dense(key, stack + (k, n), self.dtype),
                           out_shardings=sh)()
        sh_p, sh_s = (None, None) if sh is None else (sh.packed, sh.scales)
        if not stack:
            packed, scales = jax.jit(
                lambda: _draw_quantized(key, get_scheme(scheme), (), k, n),
                out_shardings=(sh_p, sh_s))()
            return QLinear(packed, scales, scheme, (k, n), name)
        abstract = AbstractMaker().dense(name, stack, k, n, scheme)
        packed = jnp.zeros(abstract.packed.shape, abstract.packed.dtype,
                           device=sh_p)
        scales = jnp.zeros(abstract.scales.shape, jnp.float32, device=sh_s)
        for i in range(stack[0]):
            packed, scales = _write_quantized_slice(
                packed, scales, key, jnp.int32(i), scheme_name=scheme, k=k,
                n=n)
        return QLinear(packed, scales, scheme, (k, n), name)

    def table(self, name, stack, rows, cols, scale=0.02):
        key = self._next()
        return jax.jit(
            lambda: _draw_table(key, stack + (rows, cols), scale, self.dtype),
            out_shardings=self._placement("table", name, stack, rows, cols,
                                          scale))()

    def norm(self, name, stack, dim):
        return jnp.ones(stack + (dim,), jnp.float32,
                        device=self._placement("norm", name, stack, dim))

    def vector(self, name, stack, dim, init=0.0):
        return jnp.full(stack + (dim,), init, jnp.float32,
                        device=self._placement("vector", name, stack, dim,
                                               init))

    def bias(self, name, stack, n):
        key = self._next()
        return jax.jit(lambda: _draw_bias(key, stack + (n,)),
                       out_shardings=self._placement("bias", name, stack,
                                                     n))()


def _draw_bias(key, shape):
    """N(0, 0.02^2) f32 biases: non-zero, so a dropped bias shows."""
    return jax.random.normal(key, shape, jnp.float32) * 0.02


def _draw_dense(key, shape, dtype):
    """N(0, 1/k) weights [..., k, n] in ``dtype``."""
    w = jax.random.normal(key, shape, jnp.float32)
    return (w / np.sqrt(shape[-2])).astype(dtype)


def _draw_table(key, shape, scale: float, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def _draw_quantized(key, scheme: QuantScheme, stack, k: int, n: int):
    """Draw N(0, 1/k) weights [*stack, k, n] and quantize each [k, n]."""
    w = jax.random.normal(key, stack + (k, n), jnp.float32) / np.sqrt(k)
    quantize = lambda m: quantize_weights_jnp(scheme, m)   # noqa: E731
    for _ in stack:
        quantize = jax.vmap(quantize)
    return quantize(w)


@functools.partial(jax.jit, static_argnames=("scheme_name", "k", "n"),
                   donate_argnums=(0, 1))
def _write_quantized_slice(packed, scales, key, i, *, scheme_name: str,
                           k: int, n: int):
    """Draw slice ``i`` of the leading stack dim from ``fold_in(key, i)``
    and quantize it into the donated stacks (their shardings carry over)."""
    p, s = _draw_quantized(jax.random.fold_in(key, i),
                           get_scheme(scheme_name), packed.shape[1:-2], k, n)
    start = (i,) + (0,) * p.ndim
    return (jax.lax.dynamic_update_slice(packed, p[None], start),
            jax.lax.dynamic_update_slice(scales, s[None], start))


class AbstractMaker(Maker):
    """ShapeDtypeStruct trees — dry-run parameter specs, zero allocation."""

    def __init__(self, quantize: bool = True, dtype=jnp.bfloat16):
        self.quantize = quantize
        self.dtype = dtype

    def dense(self, name, stack, k, n, scheme=None):
        if scheme is None or scheme == "bf16" or not self.quantize:
            return jax.ShapeDtypeStruct(stack + (k, n), self.dtype)
        s = get_scheme(scheme)
        from repro.quant.schemes import effective_group
        group = effective_group(s.group_size, k)
        if s.packed:
            per = 32 // s.weight_bits
            packed = jax.ShapeDtypeStruct(stack + (k // per, n), jnp.int32)
        else:  # w8a8 raw int8
            packed = jax.ShapeDtypeStruct(stack + (k, n), jnp.int8)
        scales = jax.ShapeDtypeStruct(stack + (k // group, n), jnp.float32)
        return QLinear(packed, scales, scheme, (k, n), name)

    def table(self, name, stack, rows, cols, scale=0.02):
        return jax.ShapeDtypeStruct(stack + (rows, cols), self.dtype)

    def norm(self, name, stack, dim):
        return jax.ShapeDtypeStruct(stack + (dim,), jnp.float32)

    def vector(self, name, stack, dim, init=0.0):
        return jax.ShapeDtypeStruct(stack + (dim,), jnp.float32)

    def bias(self, name, stack, n):
        return jax.ShapeDtypeStruct(stack + (n,), jnp.float32)


class PspecMaker(Maker):
    """PartitionSpec trees.  Axis names resolved via a rule callback
    mapping the logical axes of each leaf to mesh axes."""

    def __init__(self, rule: Callable[[str, int], Optional[str]],
                 quantize: bool = True):
        self.rule = rule      # (leaf_name, logical_dim_index) -> mesh axis
        self.quantize = quantize

    def _spec(self, name, stack, dims: int) -> P:
        parts = [None] * len(stack) + [self.rule(name, d) for d in range(dims)]
        return P(*parts)

    def dense(self, name, stack, k, n, scheme=None):
        if scheme is None or scheme == "bf16" or not self.quantize:
            return self._spec(name, stack, 2)
        # packed codes and scales have different K-dim sizes than the
        # logical weight; the rule sees them under suffixed names so
        # divisibility is checked against the actual array dims
        spec_p = self._spec(name + "@packed", stack, 2)
        spec_s = self._spec(name + "@scales", stack, 2)
        return QLinear(spec_p, spec_s, scheme, (k, n), name)

    def table(self, name, stack, rows, cols, scale=0.02):
        return self._spec(name, stack, 2)

    def norm(self, name, stack, dim):
        return P(*([None] * len(stack) + [self.rule(name, 0)]))

    def vector(self, name, stack, dim, init=0.0):
        return P(*([None] * len(stack) + [self.rule(name, 0)]))

    def bias(self, name, stack, n):
        return P(*([None] * len(stack) + [self.rule(name, 0)]))


# ---------------------------------------------------------------------------
# Numerics helpers
# ---------------------------------------------------------------------------
def rms_norm(x, gamma, eps: float = 1e-6):
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps) * gamma).astype(x.dtype)


def layer_norm(x, gamma, beta, eps: float = 1e-5):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    return ((xf - mu) * jax.lax.rsqrt(var + eps) * gamma + beta).astype(x.dtype)


def activate(kind: str, x):
    if kind == "silu":
        return jax.nn.silu(x)
    if kind == "gelu":
        return jax.nn.gelu(x)
    if kind == "relu2":  # nemotron squared-ReLU
        r = jax.nn.relu(x)
        return r * r
    raise ValueError(kind)


def rope_frequencies(d_head: int, theta: float = 10000.0):
    return 1.0 / (theta ** (jnp.arange(0, d_head, 2, dtype=jnp.float32) / d_head))


def apply_rope(x, positions, theta: float = 10000.0):
    """x [..., S, H, D]; positions [..., S] int32 -> rotated x (same dtype)."""
    d = x.shape[-1]
    freqs = rope_frequencies(d, theta)                       # [D/2]
    angles = positions[..., :, None].astype(jnp.float32) * freqs  # [..., S, D/2]
    cos = jnp.cos(angles)[..., :, None, :]
    sin = jnp.sin(angles)[..., :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def sinusoidal_positions(n_pos: int, dim: int):
    pos = np.arange(n_pos)[:, None]
    i = np.arange(dim // 2)[None, :]
    angle = pos / np.power(10000.0, 2 * i / dim)
    emb = np.concatenate([np.sin(angle), np.cos(angle)], axis=-1)
    return jnp.asarray(emb, jnp.float32)
