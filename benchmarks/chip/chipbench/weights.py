"""Seeded weights: the program's served form and the reference's floats.

Every leaf is drawn from ``leaf_key(seed, path)``; a stacked leaf draws
layer ``l`` from ``fold_in(leaf key, l)``.  ``make_params`` builds the
program's whole parameter tree on the device in ONE jitted call, in the
type it is served in (packed int32 code words and f32 scales for the
quantized linears, bf16 tables, f32 norms).  ``dense_weight`` and
``vector`` redraw the same values for the plain reference, one layer at a
time, from the same key — the reference never reads what the program
holds.

Quantized linears (format ``int4``) are drawn as codes, not as floats to
be quantized: codes read as two's complement, uniform over -7..7 with zero
taking the place of -8 (so the weights have mean zero: a lopsided code
range adds a common offset to every output, and over 36 layers the
residual stream collapses onto one direction), and one f32 scale per
``group`` inputs of each output column.

The codes reach the program through its own packing routine
(``repro.quant.pack.pack_codes``), so the benchmark does not depend on
how the words are laid out; the reference decodes the codes it drew.
"""
from __future__ import annotations

import zlib
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

# root-mean-square code value, which sets the scale for an N(0, 1/K)-sized
# weight, over the codes as they are drawn
CODE_RMS = float(np.sqrt(np.mean(np.where(
    np.arange(-8, 8) == -8, 0, np.arange(-8, 8)) ** 2.0)))


def base_key(seed: int):
    """A PRNG key from any non-negative integer seed (wider than 32 bits)."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def leaf_key(key, path: str):
    return jax.random.fold_in(key, zlib.crc32(path.encode()) & 0x7FFFFFFF)


def check_format(w: Dict) -> None:
    """Only int4 codes with f32 scales are drawn."""
    if (w["format"], w["scale"]) != ("int4", "f32"):
        raise ValueError(f"no rule to draw weights of format {w}")


def draw_codes(key, k: int, n: int):
    """[K, N] int32 int4 codes 0..15 (two's complement, never 8) of one
    layer of a quantized linear."""
    bits = jax.random.bits(key, (k // 8, n), jnp.uint32)
    codes = jnp.stack([(bits >> (4 * i)) & 15 for i in range(8)], axis=1)
    codes = codes.reshape(k, n).astype(jnp.int32)
    return jnp.where(codes == 8, 0, codes)


def draw_scales(key, k: int, n: int, group: int):
    """[K / group, N] f32 scales of one layer of a quantized linear."""
    target = 1.0 / (CODE_RMS * np.sqrt(k))
    u = jax.random.uniform(key, (k // group, n), jnp.float32, -0.5, 0.5)
    return (target * jnp.exp2(u)).astype(jnp.float32)


def decode_codes(codes):
    """int4 codes -> f32 values, plainly, as two's complement."""
    return jnp.where(codes >= 8, codes - 16, codes).astype(jnp.float32)


def dense_weight(key, layer, k: int, n: int, w: Dict):
    """The reference's f32 [K, N] weight of layer ``layer`` of a quantized
    linear whose format is ``w`` ({"format", "group", "scale"})."""
    check_format(w)
    lk = jax.random.fold_in(key, layer)
    codes = draw_codes(jax.random.fold_in(lk, 0), k, n)
    scales = draw_scales(jax.random.fold_in(lk, 1), k, n, w["group"])
    vals = decode_codes(codes)
    return (vals.reshape(k // w["group"], w["group"], n)
            * scales[:, None, :]).reshape(k, n)


def table(key, rows: int, cols: int, scale: float):
    """bf16 [rows, cols] N(0, scale^2) table (embedding, lm_head)."""
    return (jax.random.normal(key, (rows, cols), jnp.float32)
            * scale).astype(jnp.bfloat16)


def vector(key, layer, dim: int):
    """f32 [dim] norm gain of one layer: 1 + N(0, 0.1^2)."""
    k = key if layer is None else jax.random.fold_in(key, layer)
    return 1.0 + 0.1 * jax.random.normal(k, (dim,), jnp.float32)


def _path(kp) -> str:
    return "/".join(str(getattr(p, "key", getattr(p, "idx", p))) for p in kp)


def make_params(cfg_file: Dict, cfg, seed: int):
    """The program's parameter tree for ``cfg``, built on the device in one
    jitted call from ``seed``.  The tree's structure and shapes come from
    the program (``AbstractMaker``); every leaf must be one this module
    knows how to draw."""
    from repro.models import transformer as T
    from repro.models.common import AbstractMaker, QLinear
    from repro.quant.pack import pack_codes

    abstract = T.build_params(cfg, AbstractMaker())
    is_q = lambda x: isinstance(x, QLinear)  # noqa: E731
    flat, treedef = jax.tree_util.tree_flatten_with_path(abstract,
                                                         is_leaf=is_q)
    d = cfg.d_model
    fmts = {"attn": cfg_file["weights"]["proj"],
            "ffn": cfg_file["weights"]["ffn"]}
    for w in fmts.values():
        check_format(w)

    def draw(key):
        out = []
        for kp, leaf in flat:
            path = _path(kp)
            lkey = leaf_key(key, path)
            if is_q(leaf):
                w = fmts[path.split("/")[1]]
                n_layers = leaf.packed.shape[0]
                k, n = leaf.shape
                if leaf.scales.shape != (n_layers, k // w["group"], n):
                    raise ValueError(f"{path}: the program holds scales "
                                     f"{leaf.scales.shape}, the file states "
                                     f"{w}")

                def one(layer, lkey=lkey, k=k, n=n, w=w):
                    lk = jax.random.fold_in(lkey, layer)
                    codes = draw_codes(jax.random.fold_in(lk, 0), k, n)
                    return (pack_codes(codes, 4),
                            draw_scales(jax.random.fold_in(lk, 1), k, n,
                                        w["group"]))
                packed, scales = jax.lax.map(one, jnp.arange(n_layers))
                out.append((packed.astype(leaf.packed.dtype),
                            scales.astype(leaf.scales.dtype)))
            elif path == "embed":
                out.append(table(lkey, *leaf.shape, 1.0))
            elif path == "lm_head":
                out.append(table(lkey, *leaf.shape, 1.0 / np.sqrt(d)))
            elif path.endswith("/g"):
                if len(leaf.shape) == 1:
                    out.append(vector(lkey, None, leaf.shape[0]))
                else:
                    out.append(jax.vmap(lambda l, lkey=lkey, dim=leaf.shape[1]:
                                        vector(lkey, l, dim))(
                        jnp.arange(leaf.shape[0])))
            else:
                raise ValueError(f"no rule to draw parameter {path!r}")
        return out

    drawn = jax.jit(draw)(base_key(seed))
    leaves = []
    for (kp, leaf), arr in zip(flat, drawn):
        if is_q(leaf):
            leaves.append(QLinear(arr[0], arr[1], leaf.scheme_name,
                                  leaf.shape, leaf.name))
        else:
            if arr.shape != leaf.shape or arr.dtype != leaf.dtype:
                raise ValueError(f"{_path(kp)}: drew {arr.shape} {arr.dtype}"
                                 f", the program holds {leaf}")
            leaves.append(arr)
    return jax.tree_util.tree_unflatten(treedef, leaves)
