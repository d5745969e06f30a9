"""Public jit'd entry points for the XtraMAC kernels.

``quantized_matmul`` is the single dispatch the model layer calls: it picks
the kernel (or the pure-jnp reference path) from the layer's quantization
scheme plus the active *execution policy*.  The jnp path is mathematically
identical — packed weights either way, so HBM traffic (the roofline memory
term) is the same; the Pallas path is the TPU fast path, run in interpret
mode on any other backend (``repro.kernels.resolve_interpret``).

Execution policy (DESIGN.md §12, §14).  Dispatch is driven by ONE
module-level execution record:

    _EXEC = {mode: 'auto'|'jnp'|'pallas', mesh: Mesh|None, partitioned: bool}

``declare_execution(kernel=..., mesh=...)`` is the single writer — drivers
resolve a ``PrecisionPolicy.kernel`` and declare their mesh before tracing.
Under a declared multi-device mesh the Pallas kernels run inside
``shard_map``: each shard executes the unmodified kernel on its
shard-local block (KV heads / slots for decode attention; the
N- or K-sharded packed weight panel for the matvec path), so 'pallas' is a
first-class mesh citizen (DESIGN.md §14) — the historical blanket
downgrade is gone.  ``kernel: 'auto'`` resolves to pallas under a mesh.
On a single device it resolves per site: a packed quantized weight's
product (awq_int4 / mxfp4 / fp8) takes the packed kernel where the backend
compiles Pallas natively (a TPU), the shapes tile it and M is at most
``AUTO_KERNEL_MAX_ROWS``; everything else — decode attention, w8a8, larger
M, any CPU — takes the jnp reference path.  ``site_paths()`` records, at
trace time, which path each quantized product's site took.

What remains of the downgrade is PER-SITE: a call site whose shard-local
shapes cannot tile the kernel legally — or that has no registered
sharding spec (stacked-expert leaves, ad-hoc callers) — falls back to the
jnp path with a ``KernelFallbackWarning`` keyed by the site (once per
site per process; other sites in the same trace keep the kernel) — a
driver that must run every site on the kernel turns that category into
an error.  ``partitioned=True``
without a mesh (the legacy shim spelling) still downgrades every site:
with no mesh object there is nothing to shard_map over.

``set_use_kernel`` (models/common.py) and ``set_under_partitioning`` /
``kernel_allowed`` below survive as thin deprecation shims over
``declare_execution`` — no serve-path code calls them.
"""
from __future__ import annotations

import warnings
from typing import Optional

import jax
import jax.numpy as jnp

from repro.quant.schemes import (
    QuantizedLinearWeights, quantize_activations_int8,
)
from . import pallas_native, ref
from .decode_attention import gqa_decode_attention  # noqa: F401  (re-export)
from .packed_matmul import (
    packed_gemv, packed_matmul, packed_shapes_legal, w8a8_matmul,
)
from .xtramac_mac import virtual_dsp_multiply  # noqa: F401  (re-export)

_UNSET = object()

_EXEC = {"mode": "auto", "mesh": None, "partitioned": False}
# leaf name -> {'packed': (k_ax, n_ax), 'scales': (k_ax, n_ax)} mesh axes
# for the shard_map'd weight kernels (partitioning.serve_weight_kernel_specs)
_WSPECS = {"map": None}
_WARNED_SITES: set = set()
# quantized-product site -> 'pallas' | 'jnp': the path its last trace took
_SITE_PATHS: dict = {}

# Under kernel='auto' on one device, a packed weight's product runs the
# Pallas kernel at up to this many rows (M: the decode slots, or the tokens
# of a prefill chunk) and the jnp dequantize-and-dot above it: the largest
# M timed on the chip.  One awq_int4 4096 x 14336 product in a layer scan
# on a TPU v5e (benchmarks/packed_kernel_bench.py), ms, kernel / jnp:
# M = 16: 0.150 / 2.340; M = 64: 0.156 / 2.344; M = 256: 0.298 / 2.375.
AUTO_KERNEL_MAX_ROWS = 256


class KernelFallbackWarning(UserWarning):
    """A kernel site took the jnp path although the policy asked for
    Pallas; ``site`` names it."""

    def __init__(self, site: str, msg: str):
        super().__init__(
            f"kernel site {site!r} falls back to the jnp path: {msg} "
            "(same math, packed weights either way; warned once per site)")
        self.site = site


def declare_execution(*, kernel: Optional[str] = None,
                      partitioned: Optional[bool] = None,
                      mesh=_UNSET, weight_specs=_UNSET) -> None:
    """Declare the execution context for subsequent traces.

    ``kernel``: 'jnp' | 'pallas' pin the dispatch mode; 'auto' resets it
    to the default (per site on a single device, pallas under a mesh);
    None leaves it as-is (so an engine with an 'auto' policy inherits
    whatever a driver pinned).  ``mesh``: the jax.sharding.Mesh model
    steps are traced under (None = single device) — setting it also sets
    ``partitioned``.  ``weight_specs``: the per-leaf kernel sharding map
    from ``partitioning.serve_weight_kernel_specs`` (None to clear).
    ``partitioned`` alone (no mesh) is the legacy shim spelling: it marks
    partitioned execution with nothing to shard_map over, so every kernel
    site falls back to jnp (with a per-site warning).
    """
    if kernel in ("jnp", "pallas", "auto"):
        _EXEC["mode"] = kernel
    elif kernel is not None:
        raise ValueError(
            f"kernel={kernel!r}; valid: 'auto', 'jnp', 'pallas'")
    if partitioned is not None:
        _EXEC["partitioned"] = bool(partitioned)
    if mesh is not _UNSET:
        _EXEC["mesh"] = mesh
        _EXEC["partitioned"] = mesh is not None and mesh.size > 1
    if weight_specs is not _UNSET:
        _WSPECS["map"] = weight_specs


def reset_execution() -> None:
    """Restore the default execution declaration (mode 'auto', no mesh,
    no weight specs).  A process-level driver pin (e.g. a test that
    declared ``kernel='pallas'``) otherwise outlives its owner — any
    later 'auto'-policy engine in the same process would silently inherit
    it.  The test suite resets around every test (conftest autouse) so
    kernel-mode assertions are collection-order-independent."""
    _EXEC.update(mode="auto", mesh=None, partitioned=False)
    _WSPECS["map"] = None


def kernel_mode() -> str:
    return _EXEC["mode"]


def under_partitioning() -> bool:
    return _EXEC["partitioned"]


def active_mesh():
    """The declared mesh when it is multi-device, else None."""
    m = _EXEC["mesh"]
    return m if (m is not None and m.size > 1) else None


def resolved_kernel_mode() -> str:
    """The mode pinned, or 'auto' resolved for every site but a meshless
    quantized product (which ``_auto_weight_kernel`` decides): jnp on a
    single device, pallas under a declared multi-device mesh (the serving
    fast path — per-site legality still applies)."""
    mode = _EXEC["mode"]
    if mode != "auto":
        return mode
    return "pallas" if active_mesh() is not None else "jnp"


# ---------------------------------------------------------------------------
# Per-site fallback warnings (replaces the per-process downgrade latch)
# ---------------------------------------------------------------------------
def _warn_site(site: str, msg: str) -> None:
    if site in _WARNED_SITES:
        return
    _WARNED_SITES.add(site)
    warnings.warn(KernelFallbackWarning(site, msg), stacklevel=3)


def reset_site_warnings() -> None:
    """Re-arm the per-site fallback warnings and clear the record of each
    site's path (tests)."""
    _WARNED_SITES.clear()
    _SITE_PATHS.clear()


def site_paths() -> dict:
    """{quantized-product site: 'pallas' | 'jnp'}, the path each site took
    when it was last traced (a site is the weight's leaf name, e.g.
    'ffn.w_up')."""
    return dict(_SITE_PATHS)


def kernel_allowed(use_kernel: bool) -> bool:
    """Deprecated shim for EXPLICIT ``use_kernel`` bools: a raw kernel
    request is downgraded whenever partitioned execution is declared —
    direct callers bypass the shard_map dispatch, so running the bare
    kernel under a mesh would index shard-local views with global shapes.
    Policy-driven dispatch (``use_kernel=None``) shard_maps instead."""
    if use_kernel and _EXEC["partitioned"]:
        _warn_site(
            "<explicit use_kernel>",
            "explicit use_kernel=True under partitioned execution; use the "
            "policy dispatch (use_kernel=None), which shard_maps the kernel "
            "over the declared mesh")
        return False
    return use_kernel


def active_kernel() -> bool:
    """Whether this trace dispatches Pallas at eligible sites: the
    resolved mode is 'pallas' and (meshless, or a mesh is declared for
    shard_map).  Per-site shape legality is checked at each site."""
    if resolved_kernel_mode() != "pallas":
        return False
    return not (_EXEC["partitioned"] and _EXEC["mesh"] is None)


# --- deprecation shim (pre-policy API; serve path no longer calls it) ------
def set_under_partitioning(flag: bool) -> None:
    """Deprecated: use ``declare_execution(mesh=...)``."""
    declare_execution(partitioned=flag)


# ---------------------------------------------------------------------------
# Decode-attention dispatch (the models/attention.py gate)
# ---------------------------------------------------------------------------
def fused_decode_attention(q, k_cache, v_cache, kv_valid_len):
    """The fused Pallas flash-decode when the execution policy selects it,
    else None (the caller takes the einsum path).  Under a declared mesh
    the kernel runs inside ``shard_map`` — slots on 'data', KV heads on
    'model', the ``serve_pool_pspec`` layout — and is bitwise identical
    to the meshless kernel (no cross-shard collective; DESIGN.md §14)."""
    if resolved_kernel_mode() != "pallas":
        return None
    mesh = _EXEC["mesh"]
    if _EXEC["partitioned"] and mesh is None:
        _warn_site(
            "decode_attention",
            "pallas under partitioned execution with no declared mesh — "
            "nothing to shard_map over")
        return None
    if mesh is not None and mesh.size > 1:
        from .decode_attention import sharded_gqa_decode_attention
        return sharded_gqa_decode_attention(q, k_cache, v_cache,
                                            kv_valid_len, mesh=mesh)
    return gqa_decode_attention(q, k_cache, v_cache, kv_valid_len)


# ---------------------------------------------------------------------------
# Weight-path dispatch
# ---------------------------------------------------------------------------
def _axis_size(mesh, ax) -> int:
    if ax is None:
        return 1
    size = 1
    for a in (ax if isinstance(ax, tuple) else (ax,)):
        size *= mesh.shape[a]
    return size


def _mesh_quantized_matmul(x2, qw: QuantizedLinearWeights, mesh,
                           interpret, site: str):
    """The packed kernel under ``shard_map`` over the declared mesh, with
    specs from the registered per-leaf map; None when this site must fall
    back to the jnp path (no spec / illegal shard-local shapes).

    Activations stay replicated across the mesh (the serving matvec is
    weight-bound; sharding x rows would flip the GEMV/matmul block plan
    per data shard and break the meshless bit-exactness contract).
    N-sharded weights run a local kernel and keep the output N-sharded —
    bitwise equal to the meshless kernel (the K loop is untouched).
    K-sharded weights (split at the joint code-word/scale-group
    boundaries ``param_specs`` enforces) compute f32 partials and psum
    over the model axis — ``ref.sharded_packed_matmul_ref`` is the
    matching oracle.
    """
    from jax.sharding import PartitionSpec as P

    scheme = qw.scheme
    k, n = qw.shape
    entry = (_WSPECS["map"] or {}).get(qw.name) if qw.name else None
    if entry is None:
        _warn_site(site, "no kernel sharding spec registered for this "
                   "weight under the declared mesh (stacked-expert leaf "
                   "or unregistered call site)")
        return None
    k_ax, n_ax = entry["packed"]
    sk_ax = entry["scales"][0]
    ksz, nsz = _axis_size(mesh, k_ax), _axis_size(mesh, n_ax)

    if scheme.name == "w8a8":
        if k_ax is not None:   # per-channel scales cannot K-shard
            _warn_site(site, "w8a8 weights cannot K-shard (per-channel "
                       "scales have no K rows to split)")
            return None
        x_codes, x_scale = quantize_activations_int8(x2)
        if nsz == 1:   # nothing shards: bare kernel (GSPMD replicates it)
            return w8a8_matmul(x_codes, x_scale, qw.packed, qw.scales,
                               interpret=interpret)
        fn = jax.shard_map(
            lambda xc, xs, wc, ws: w8a8_matmul(xc, xs, wc, ws,
                                               interpret=interpret),
            mesh=mesh,
            in_specs=(P(None, None), P(), P(None, n_ax), P(None, n_ax)),
            out_specs=P(None, n_ax), check_vma=False)
        return fn(x_codes, x_scale, qw.packed, qw.scales)

    if not packed_shapes_legal(x2.shape[0], k // ksz, n // nsz, scheme):
        _warn_site(site, f"shard-local shapes (K={k // ksz}, N={n // nsz}) "
                   "cannot tile the packed kernel")
        return None
    per = 32 // scheme.weight_bits
    gemv = x2.shape[0] <= 8   # same block-plan predicate as meshless
    if ksz == 1 and nsz == 1:  # nothing shards: bare kernel, no shard_map
        return (packed_gemv if gemv else packed_matmul)(
            x2, qw, interpret=interpret)

    def local_mm(x2, packed, scales):
        qloc = QuantizedLinearWeights(
            scheme, packed, scales, (packed.shape[0] * per, packed.shape[1]))
        out = (packed_gemv if gemv else packed_matmul)(
            x2, qloc, interpret=interpret)
        return jax.lax.psum(out, k_ax) if k_ax is not None else out

    fn = jax.shard_map(local_mm, mesh=mesh,
                       in_specs=(P(None, k_ax), P(k_ax, n_ax), P(sk_ax, n_ax)),
                       out_specs=P(None, n_ax), check_vma=False)
    return fn(x2, qw.packed, qw.scales)


def quantized_matmul(x, qw: QuantizedLinearWeights, *,
                     use_kernel: Optional[bool] = None,
                     interpret=None, out_dtype=jnp.bfloat16):
    """x [..., K] @ quantized W [K, N] -> [..., N] in ``out_dtype``.

    ``use_kernel=None`` (the model layer's call) dispatches on the active
    execution policy — shard_map'd over the declared mesh, falling back
    per-site; an explicit bool overrides the mode but is downgraded under
    partitioned execution (``kernel_allowed``).  ``interpret``: None
    follows the platform (``repro.kernels.resolve_interpret``).  Scheme
    dispatch (paper Table I):
      awq_int4 / mxfp4 : INTx/FP4 x BF16 -> packed sub-byte kernel
      fp8              : FP8 weights (per-channel scale) -> packed kernel
      w8a8             : INT8 x INT8 -> INT32 (activations quantized here)
      bf16             : dense bf16 matmul (attention-path MACs)
    """
    scheme = qw.scheme
    lead = x.shape[:-1]
    k = x.shape[-1]
    x2 = x.reshape(-1, k)

    if scheme.name == "bf16":
        out = jnp.dot(x2.astype(jnp.bfloat16), qw.packed)
        return out.reshape(*lead, -1).astype(out_dtype)

    # every op of a quantized weight's product, whatever implements it
    # (jnp dequantize-and-dot, the Pallas kernel, its shard_map), carries
    # this scope in its op_name: the benchmark reads device time by it
    with jax.named_scope("qmatmul"):
        out = _quantized_dot(x2, qw, use_kernel, interpret)
        return out.reshape(*lead, -1).astype(out_dtype)


def _auto_weight_kernel(x2, qw: QuantizedLinearWeights) -> bool:
    """'auto' on a single device: whether this product runs the packed
    kernel — the backend compiles Pallas natively, the weight is packed,
    its shapes tile the kernel and M is at most ``AUTO_KERNEL_MAX_ROWS``."""
    k, n = qw.shape
    m = x2.shape[0]
    return (pallas_native() and qw.scheme.packed
            and packed_shapes_legal(m, k, n, qw.scheme)
            and m <= AUTO_KERNEL_MAX_ROWS)


def _quantized_dot(x2, qw: QuantizedLinearWeights, use_kernel, interpret):
    """x2 [M, K] @ quantized W -> [M, N] (f32 or bf16) for a non-bf16
    scheme: the dispatch of ``quantized_matmul``.  Records the path the
    site took (``site_paths``)."""
    scheme = qw.scheme
    site = qw.name or f"<{scheme.name} linear K={x2.shape[-1]}>"
    if use_kernel is None:
        mesh = _EXEC["mesh"]
        if _EXEC["mode"] == "auto" and active_mesh() is None \
                and not _EXEC["partitioned"]:
            use_kernel = _auto_weight_kernel(x2, qw)
        elif resolved_kernel_mode() != "pallas":
            use_kernel = False
        elif _EXEC["partitioned"] and mesh is None:
            _warn_site(site, "pallas under partitioned execution with "
                       "no declared mesh — nothing to shard_map over")
            use_kernel = False
        elif mesh is not None and mesh.size > 1:
            out = _mesh_quantized_matmul(x2, qw, mesh, interpret, site)
            if out is not None:
                _SITE_PATHS[site] = "pallas"
                return out
            use_kernel = False
        else:
            use_kernel = True
    else:
        use_kernel = kernel_allowed(use_kernel)
    _SITE_PATHS[site] = "pallas" if use_kernel else "jnp"

    if scheme.name == "w8a8":
        x_codes, x_scale = quantize_activations_int8(x2)
        if use_kernel:
            return w8a8_matmul(x_codes, x_scale, qw.packed, qw.scales,
                               interpret=interpret)
        return ref.w8a8_matmul_ref(x_codes, x_scale, qw.packed, qw.scales)
    if scheme.packed:  # awq_int4 / mxfp4 / fp8 — sub-byte/byte packed words
        if use_kernel:
            fn = packed_gemv if x2.shape[0] <= 8 else packed_matmul
            return fn(x2, qw, interpret=interpret)
        # jnp fallback: dequantize INTO bf16 — exactly the paper's
        # Stage-1 mapping (the INTxFP product's FP side is BF16), the
        # operand the Pallas kernel builds in VMEM
        from repro.quant.schemes import dequantize
        w = dequantize(qw, dtype=jnp.bfloat16)
        return jnp.dot(x2.astype(jnp.bfloat16), w)
    raise ValueError(scheme.name)
