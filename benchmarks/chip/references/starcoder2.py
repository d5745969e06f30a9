"""Plain float32 reference of StarCoder2's decoder block (arXiv:2402.19173).

It follows the architecture a configuration file states (its ``model`` and
``weights`` blocks), written out in ``jax.numpy`` with every product at
``Precision.HIGHEST``, no kernels, no cache and no batching tricks: a full
causal forward over each whole sequence, one layer at a time.  Each layer's
weights are drawn again from the seed (``chipbench.weights`` and this
module's ``draw_leaf``); nothing is read from the program under test.

    x_0 = E[tokens]
    per layer:  h = ln(x) * g1 + b1    LayerNorm, eps from the file
                q, k, v = h Wq + bq, h Wk + bk, h Wv + bv   (RoPE on q and
                          k, theta from the file, as in ``dense_gqa``)
                x += softmax(q k^T / sqrt(d_head) + causal) v  Wo + bo
                h = ln(x) * g2 + b2
                x += gelu_tanh(h Win + b_in) Wout + b_out
    logits = (ln(x) * g_f + b_f) W_lm    untied lm_head

The published model attends over a 4,096-position sliding window; the
reference attends the whole prefix, which is the same at the cell's
positions (fewer than 4,096).

``linears`` lists one layer's linears as the program names them;
``draw_leaf`` draws the LayerNorm betas and the biases, N(0, 0.1^2) each,
so that a program dropping any of them reads far from the reference.
``lower="fp8"`` is the control of the correctness check, as in
``dense_gqa``: every activation entering a product rounded to FP8 E4M3.
"""
from __future__ import annotations

import functools
import os
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import spec
from chipbench import weights as W

# the benchmark directory this module lies in, where the weight formats'
# rules and the shared helpers are looked up
BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_G = spec.load_module("references", "dense_gqa", BENCH_DIR)
HI, _round8, _rope, _lin = _G.HI, _G._round8, _G._rope, _G._lin
BIAS_STD = 0.1
# the bias leaf of each linear of a layer, by the program's names
BIAS_OF = {"attn/wq": "attn/bq", "attn/wk": "attn/bk", "attn/wv": "attn/bv",
           "attn/wo": "attn/bo", "ffn/w_in": "ffn/b_in",
           "ffn/w_out": "ffn/b_out"}


def _check(m: Dict) -> None:
    if (m["activation"], m["gated_ffn"], m["norm"], m["use_bias"],
            m["tie_embeddings"]) != ("gelu", False, "layer", True, False):
        raise ValueError("the reference has LayerNorm, biased linears, the "
                         "plain GELU FFN and an untied lm_head only")


def linears(m: Dict) -> Dict[str, Dict[str, tuple]]:
    """(K, N) of each linear of one layer, by weight kind and by the
    program's name for it."""
    _check(m)
    d, h, hk, dh, f = (m["d_model"], m["n_heads"], m["n_kv_heads"],
                       m["d_head"], m["d_ff"])
    return {"proj": {"attn/wq": (d, h * dh), "attn/wk": (d, hk * dh),
                     "attn/wv": (d, hk * dh), "attn/wo": (h * dh, d)},
            "ffn": {"ffn/w_in": (d, f), "ffn/w_out": (f, d)}}


def _vec(key, layer, n: int):
    """f32 [n] bias or beta of one layer (``layer`` None: unstacked)."""
    k = key if layer is None else jax.random.fold_in(key, layer)
    return BIAS_STD * jax.random.normal(k, (n,), jnp.float32)


def draw_leaf(path: str, shape, dtype, key):
    """The leaves the harness has no rule for: LayerNorm betas (``*/b``)
    and linear biases, drawn layer by layer from the leaf's key."""
    name = path.split("/", 1)[-1]
    if not (path.endswith("/b") or name in BIAS_OF.values()):
        raise ValueError(f"no rule to draw parameter {path!r}")
    if len(shape) == 1:
        return _vec(key, None, shape[0]).astype(dtype)
    return jax.vmap(lambda l: _vec(key, l, shape[1]))(
        jnp.arange(shape[0])).astype(dtype)


def _static(cfg_file: Dict) -> tuple:
    m, w = cfg_file["model"], cfg_file["weights"]
    _check(m)
    return (m["n_layers"], m["d_model"], m["n_heads"], m["n_kv_heads"],
            m["d_head"], m["d_ff"], m["vocab"], float(m["norm_eps"]),
            float(m["rope_theta"]),
            (w["proj"]["format"], w["proj"]["group"], w["proj"]["scale"]),
            (w["ffn"]["format"], w["ffn"]["group"], w["ffn"]["scale"]))


def _ln(x, key, path, layer, d, eps):
    g = W.vector(W.leaf_key(key, path + "/g"), layer, d)
    b = _vec(W.leaf_key(key, path + "/b"), layer, d)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * g + b


@functools.partial(jax.jit, static_argnums=(2,))
def _embed(key, tokens, st):
    d, vocab = st[1], st[6]
    e = W.table(W.leaf_key(key, "embed"), vocab, d, 1.0)
    return e[tokens].astype(jnp.float32)


@functools.partial(jax.jit, static_argnums=(3, 4))
def _layer(x, key, layer, st, lower):
    (_, d, h, hk, dh, f, _, eps, theta, wp, wf) = st
    wp = dict(zip(("format", "group", "scale"), wp))
    wf = dict(zip(("format", "group", "scale"), wf))

    def lin(a, name, k, n, fmt):
        wt = W.dense_weight(W.leaf_key(key, "layers/" + name), layer, k, n,
                            fmt, BENCH_DIR)
        bias = _vec(W.leaf_key(key, "layers/" + BIAS_OF[name]), layer, n)
        return _lin(a, wt, lower) + bias

    b, s, _ = x.shape
    a = _ln(x, key, "layers/ln1", layer, d, eps)
    q = lin(a, "attn/wq", d, h * dh, wp).reshape(b, s, h, dh)
    k = lin(a, "attn/wk", d, hk * dh, wp).reshape(b, s, hk, dh)
    v = lin(a, "attn/wv", d, hk * dh, wp).reshape(b, s, hk, dh)
    q, k = _rope(q, theta), _rope(k, theta)
    q, k, v = _round8(q, lower), _round8(k, lower), _round8(v, lower)
    qg = q.reshape(b, s, hk, h // hk, dh) / np.sqrt(dh)
    sc = jnp.einsum("bqhrd,bkhd->bhrqk", qg, k, precision=HI)
    causal = jnp.tril(jnp.ones((s, s), bool))
    p = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), axis=-1)
    o = jnp.einsum("bhrqk,bkhd->bqhrd", p, v, precision=HI)
    x = x + lin(o.reshape(b, s, h * dh), "attn/wo", h * dh, d, wp)
    a = _ln(x, key, "layers/ln2", layer, d, eps)
    hid = jax.nn.gelu(lin(a, "ffn/w_in", d, f, wf), approximate=True)
    return x + lin(hid, "ffn/w_out", f, d, wf)


@functools.partial(jax.jit, static_argnums=(3, 4))
def _gaps(x, key, targets, st, lower):
    """Per position: max logit minus the logit of ``targets`` under the
    reference, and the argmax of these logits."""
    d, vocab, eps = st[1], st[6], st[7]
    a = _ln(x, key, "ln_f", None, d, eps)
    lm = W.table(W.leaf_key(key, "lm_head"), d, vocab,
                 1.0 / np.sqrt(d)).astype(jnp.float32)
    logits = _lin(a, lm, lower)
    best = logits.max(-1)
    got = jnp.take_along_axis(logits, targets[..., None], -1)[..., 0]
    return best - got, logits.argmax(-1).astype(jnp.int32)


def logits_summary(cfg_file: Dict, seed: int, tokens: np.ndarray,
                   targets: np.ndarray, lower: Optional[str] = None):
    """Run the reference over ``tokens`` [B, S] (padded) and return, per
    position, (gap of ``targets`` [B, S] below the best logit, argmax)."""
    st = _static(cfg_file)
    key = W.base_key(seed)
    x = _embed(key, jnp.asarray(tokens, jnp.int32), st)
    for layer in range(st[0]):
        x = _layer(x, key, jnp.int32(layer), st, lower)
    gap, top = _gaps(x, key, jnp.asarray(targets, jnp.int32), st, lower)
    return np.asarray(gap), np.asarray(top)
