"""Seeded weights: the program's served form and the reference's floats.

Every leaf is drawn from ``leaf_key(seed, path)``; a stacked leaf draws
layer ``l`` from ``fold_in(leaf key, l)``.  ``make_params`` builds the
program's whole parameter tree on the device in ONE jitted call, in the
type it is served in (packed code words and scales for the quantized
linears, bf16 tables, f32 norms).  ``dense_weight`` and ``vector`` redraw
the same values for the plain reference, one layer at a time, from the
same key — the reference never reads what the program holds.

A quantized linear is drawn by the rules of its weight format, a module
of its own (``formats/<format>.py``, found by the name the configuration
file states): the program's leaves, packed by the program's own routine
(``repro.quant.pack.pack_codes``), so the benchmark does not depend on how
the words are laid out, and the reference's floats, decoded from the
codes it drew.  Such a module has ``check(w)`` (ValueError for what it
has no rule for), ``served(key, K, N, w)`` -> (packed words, scales) and
``dense(key, K, N, w)`` -> f32 [K, N] of one layer, and ``nbytes(K, N, w)``
(``chipbench.costs``).  A leaf that is neither a quantized linear, nor the
embedding, the lm_head or a norm gain is drawn by the configuration's
reference module, where it has ``draw_leaf(path, shape, dtype, key)``.
"""
from __future__ import annotations

import zlib
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from . import spec


def base_key(seed: int):
    """A PRNG key from any non-negative integer seed (wider than 32 bits)."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def leaf_key(key, path: str):
    return jax.random.fold_in(key, zlib.crc32(path.encode()) & 0x7FFFFFFF)


def check_format(w: Dict, bench_dir: str = spec.BENCH_DIR):
    """The rules of weight format ``w`` (``formats/<format>.py``);
    ValueError where there are none or they refuse what ``w`` states."""
    rules = spec.load_module("formats", w["format"], bench_dir)
    if rules is None:
        raise ValueError(f"no rule to draw weights of format {w}: no "
                         f"formats/{w['format']}.py")
    rules.check(w)
    return rules


def dense_weight(key, layer, k: int, n: int, w: Dict,
                 bench_dir: str = spec.BENCH_DIR):
    """The reference's f32 [K, N] weight of layer ``layer`` of a quantized
    linear whose format is ``w`` ({"format", "group", "scale"})."""
    rules = check_format(w, bench_dir)
    return rules.dense(jax.random.fold_in(key, layer), k, n,
                       spec.layer_format(w, k))


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


def make_params(cfg_file: Dict, cfg, seed: int,
                bench_dir: str = spec.BENCH_DIR):
    """The program's parameter tree for ``cfg``, built on the device in one
    jitted call from ``seed``.  The tree's structure and shapes come from
    the program (``AbstractMaker``); every leaf must be one this module,
    the weight format's rules or the reference's ``draw_leaf`` knows how
    to draw."""
    from repro.models import transformer as T
    from repro.models.common import AbstractMaker, QLinear

    abstract = T.build_params(cfg, AbstractMaker())
    is_q = lambda x: isinstance(x, QLinear)  # noqa: E731
    flat, treedef = jax.tree_util.tree_flatten_with_path(abstract,
                                                         is_leaf=is_q)
    d = cfg.d_model
    fmts = {"attn": cfg_file["weights"]["proj"],
            "ffn": cfg_file["weights"]["ffn"]}
    rules = {kind: check_format(w, bench_dir) for kind, w in fmts.items()}
    draw_leaf = getattr(spec.load_module("references", cfg_file["reference"],
                                         bench_dir), "draw_leaf", None)

    def draw(key):
        out = []
        for kp, leaf in flat:
            path = _path(kp)
            lkey = leaf_key(key, path)
            if is_q(leaf):
                kind = path.split("/")[1]
                n_layers = leaf.packed.shape[0]
                k, n = leaf.shape
                w = spec.layer_format(fmts[kind], k)
                if leaf.scales.shape != (n_layers, k // w["group"], n):
                    raise ValueError(f"{path}: the program holds scales "
                                     f"{leaf.scales.shape}, the file states "
                                     f"{fmts[kind]}")

                def one(layer, lkey=lkey, k=k, n=n, w=w, rule=rules[kind]):
                    return rule.served(jax.random.fold_in(lkey, layer), k, n,
                                       w)
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
            elif draw_leaf is not None:
                out.append(draw_leaf(path, leaf.shape, leaf.dtype, lkey))
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
