"""Quantized KV-cache storage — the serving pool's side of the paper's
mixed-precision plan.

PR 1 quantized the *weight* operand stream; this module extends the plan to
the KV cache (the operand stream that actually caps continuous-batching
throughput: slots = cache bytes / bytes-per-token).  A bf16 KV slab
``[..., S, H, D]`` becomes

  packed  [..., S, H, D/4]  int32  — 4 8-bit codes per word, little-endian
                                     (quant/pack.py's HBM-word layout,
                                     applied along ``d_head``)
  scales  [..., S, H]       f32    — one absmax scale per (position, head)
                                     group (DESIGN.md §9)

``QuantizedKV`` carries the pair as one pytree node (scheme name as static
aux data), so the pool cache tree flows through ``jax.lax.scan`` layer
stacks, ``tree_map`` slot slicing and buffer donation exactly like a plain
array slab.  Quantize-on-write happens inside the jitted prefill/decode
steps via ``cache_write_slice`` / ``cache_write_rows``; ``cache_read`` is
the dequantized dense view (the einsum-oracle read path — the Pallas
decode kernel instead streams ``packed``/``scales`` directly and
dequantizes in-kernel, see ``kernels/decode_attention.py``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .schemes import get_kv_scheme, kv_dequantize, kv_quantize


@jax.tree_util.register_pytree_node_class
class QuantizedKV:
    """One quantized KV slab as a pytree node: children = (packed, scales),
    static aux = scheme name — jit/scan/donation-safe (mirrors QLinear)."""

    def __init__(self, packed, scales, scheme_name: str):
        self.packed = packed
        self.scales = scales
        self.scheme_name = scheme_name

    def tree_flatten(self):
        return (self.packed, self.scales), (self.scheme_name,)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children[0], children[1], aux[0])

    def __repr__(self):
        shape = getattr(self.packed, "shape", None)
        return f"QuantizedKV({self.scheme_name}, packed{shape})"


def kv_dtype_name(kv_dtype) -> str:
    """Canonical string name of the pool dtype knob ('bf16'|'int8'|'fp8')."""
    scheme = get_kv_scheme(kv_dtype)
    return scheme.name if scheme is not None else "bf16"


def kv_slab_pspec(axes, kv_dtype):
    """PartitionSpec twin of ``kv_slab_spec``: same tree shape (a
    ``QuantizedKV`` node for quantized dtypes, a bare spec otherwise), so
    sharding specs can never drift structurally from the slab they annotate.

    ``axes``: one mesh axis (or None) per *logical* slab dim
    [..., S, H, D].  For quantized slabs the trailing ``d_head`` dim packs
    4 codes per int32 word, so sharding it would split inside code words —
    it must be None; the scales twin simply drops that dim.
    """
    from jax.sharding import PartitionSpec as P
    scheme = get_kv_scheme(kv_dtype)
    if scheme is None:
        return P(*axes)
    assert axes[-1] is None, \
        "quantized KV packs codes along d_head: that dim cannot shard"
    return QuantizedKV(P(*axes), P(*axes[:-1]), scheme.name)


def kv_slab_spec(shape, kv_dtype):
    """ShapeDtypeStruct spec(s) for one KV slab of logical ``shape``
    [..., S, H, D] stored as ``kv_dtype`` ('bf16' / legacy jnp dtype / a
    KV scheme name).  Quantized slabs require ``D % 4 == 0`` (packing)."""
    scheme = get_kv_scheme(kv_dtype)
    if scheme is None:
        dt = kv_dtype if not isinstance(kv_dtype, str) and kv_dtype is not None \
            else jnp.bfloat16
        return jax.ShapeDtypeStruct(shape, dt)
    d = shape[-1]
    assert d % 4 == 0, f"d_head {d} not divisible by 4 (KV code packing)"
    return QuantizedKV(
        jax.ShapeDtypeStruct(shape[:-1] + (d // 4,), jnp.int32),
        jax.ShapeDtypeStruct(shape[:-1], jnp.float32),
        scheme.name,
    )


# ---------------------------------------------------------------------------
# Write / read paths (jnp; used inside the jitted engine steps)
# ---------------------------------------------------------------------------
@jax.named_scope("kv_write")
def cache_write_slice(slab, vals, offset):
    """Write ``vals`` [B, S, ...] into ``slab`` at sequence position
    ``offset`` (axis 1) — the prefill/prefill-chunk write.  Quantized slabs
    quantize-on-write (per-position scales make the result independent of
    what else shares the write, so chunked and whole-prompt prefill commit
    identical bytes)."""
    if isinstance(slab, QuantizedKV):
        packed, scales = kv_quantize(get_kv_scheme(slab.scheme_name), vals)
        return QuantizedKV(
            jax.lax.dynamic_update_slice_in_dim(slab.packed, packed, offset,
                                                axis=1),
            jax.lax.dynamic_update_slice_in_dim(slab.scales, scales, offset,
                                                axis=1),
            slab.scheme_name)
    return jax.lax.dynamic_update_slice_in_dim(
        slab, vals.astype(slab.dtype), offset, axis=1)


@jax.named_scope("kv_write")
def cache_write_rows(slab, vals, rows, offsets):
    """Per-row scatter (decode): row i of ``vals`` [B, 1, ...] lands at
    ``slab[i, offsets[i]]`` — every pool slot writes at its own length."""
    if isinstance(slab, QuantizedKV):
        packed, scales = kv_quantize(get_kv_scheme(slab.scheme_name), vals)
        return QuantizedKV(
            slab.packed.at[rows, offsets].set(packed[:, 0]),
            slab.scales.at[rows, offsets].set(scales[:, 0]),
            slab.scheme_name)
    return slab.at[rows, offsets].set(vals[:, 0].astype(slab.dtype))


def cache_read(slab, dtype=jnp.bfloat16):
    """Dense view of a slab: dequantize QuantizedKV (the einsum-oracle read
    path — one materialized [B, S, H, D] per layer), pass bf16 through."""
    if isinstance(slab, QuantizedKV):
        return kv_dequantize(get_kv_scheme(slab.scheme_name),
                             slab.packed, slab.scales, dtype)
    return slab


# ---------------------------------------------------------------------------
# Paged indirection (serve/kv_pool.PagedKVPool; DESIGN.md §15)
# ---------------------------------------------------------------------------
def gather_pages(arena, table):
    """Materialize the virtual KV slab of every slot from a page arena.

    ``arena``: one layer's page arena [n_pages, page_size, ...] (bare array
    or ``QuantizedKV`` — codes and scales gather in lockstep).  ``table``:
    [n_slots, pages_per_slot] int32 page ids.  Returns the *virtual slab*
    [n_slots, pages_per_slot * page_size, ...] — exactly the layout the
    slab pool stores directly, so every downstream consumer (the einsum
    attention paths, the Pallas decode kernel, the write primitives above)
    runs UNCHANGED on identical bytes.  That is the paged pool's
    bit-identity argument: same committed bytes in the same [slot, pos]
    layout, garbage pages only ever gathered into positions masked by
    ``kv_valid_len``.
    """
    n_slots, pp = table.shape

    def g(a):
        v = a[table]                         # [n_slots, pp, page_size, ...]
        return v.reshape((n_slots, pp * a.shape[1]) + a.shape[2:])

    if isinstance(arena, QuantizedKV):
        return QuantizedKV(g(arena.packed), g(arena.scales),
                           arena.scheme_name)
    return g(arena)


@jax.named_scope("kv_write")
def scatter_pages(arena, table, virt):
    """Write a (possibly updated) virtual slab back through the page table.

    Inverse of ``gather_pages``: virtual position [slot, i*ps + j] lands at
    ``arena[table[slot, i], j]``.  Duplicate table entries are allowed and
    safe by the pool's invariants (DESIGN.md §15): a page shared
    copy-on-write between slots is never written through (writes hit
    private pages only, so every duplicate scatters the page's own
    unchanged bytes), and the reserved garbage page 0 — the target of every
    unmapped entry — may receive differing garbage rows, but its content is
    never gathered into an attended (< ``kv_valid_len``) position.
    """
    n_slots, pp = table.shape
    flat = table.reshape(-1)

    def s(a, v):
        return a.at[flat].set(
            v.reshape((n_slots * pp, a.shape[1]) + a.shape[2:]))

    if isinstance(arena, QuantizedKV):
        return QuantizedKV(s(arena.packed, virt.packed),
                           s(arena.scales, virt.scales), arena.scheme_name)
    return s(arena, virt)
