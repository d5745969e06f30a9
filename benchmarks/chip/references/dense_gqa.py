"""Plain float32 reference of a dense decoder with grouped-query attention.

It follows the architecture a configuration file states (its ``model`` and
``weights`` blocks), written out in ``jax.numpy`` with every product at
``Precision.HIGHEST``, no kernels, no cache and no batching tricks: a full
causal forward over each whole sequence, one layer at a time.  Each layer's
weights are drawn again from the seed (``chipbench.weights``); nothing is
read from the program under test.

    x_0 = E[tokens]
    per layer:  h = rms(x) * g1
                q, k, v = h Wq, h Wk, h Wv   (RoPE on q and k: the two
                          halves of each head rotate as (x1, x2) ->
                          (x1 cos - x2 sin, x1 sin + x2 cos), theta
                          from the file; positions 0, 1, ...)
                x += softmax(q k^T / sqrt(d_head) + causal) v  Wo
                h = rms(x) * g2
                x += (silu(h Wg) * h Wu) Wd      gated SiLU FFN, or
                x += gelu_tanh(h Win) Wout       the plain GELU FFN
    logits = (rms(x) * g_f) W_lm

``linears`` lists one layer's linears as the program names them, which
are the leaves the weights are drawn from and what ``chipbench.costs``
counts.  Any other FFN, norm or a tied lm_head is refused.

``lower="fp8"`` computes the same forward with every activation that
enters a product (the inputs of each linear, q, k and v) rounded to FP8
E4M3 with one scale per row: the control of the correctness check.
"""
from __future__ import annotations

import functools
import os
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import weights as W

HI = jax.lax.Precision.HIGHEST
# the benchmark directory this module lies in, where the weight formats'
# rules are looked up
BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FFNS = {("silu", True): "gated", ("gelu", False): "plain"}


def _ffn(m: Dict) -> str:
    ffn = FFNS.get((m["activation"], m["gated_ffn"]))
    if ffn is None or m["norm"] != "rms" or m["tie_embeddings"]:
        raise ValueError("the reference has RMSNorm, an untied lm_head and "
                         "the gated SiLU or plain GELU FFN only")
    return ffn


def linears(m: Dict) -> Dict[str, Dict[str, tuple]]:
    """(K, N) of each linear of one layer, by weight kind and by the
    program's name for it."""
    d, h, hk, dh, f = (m["d_model"], m["n_heads"], m["n_kv_heads"],
                       m["d_head"], m["d_ff"])
    attn = {"attn/wq": (d, h * dh), "attn/wk": (d, hk * dh),
            "attn/wv": (d, hk * dh), "attn/wo": (h * dh, d)}
    if _ffn(m) == "gated":
        ffn = {"ffn/w_gate": (d, f), "ffn/w_up": (d, f), "ffn/w_down": (f, d)}
    else:
        ffn = {"ffn/w_in": (d, f), "ffn/w_out": (f, d)}
    return {"proj": attn, "ffn": ffn}


def _static(cfg_file: Dict) -> tuple:
    m, w = cfg_file["model"], cfg_file["weights"]
    return (m["n_layers"], m["d_model"], m["n_heads"], m["n_kv_heads"],
            m["d_head"], m["d_ff"], m["vocab"], float(m["norm_eps"]),
            float(m["rope_theta"]),
            (w["proj"]["format"], w["proj"]["group"], w["proj"]["scale"]),
            (w["ffn"]["format"], w["ffn"]["group"], w["ffn"]["scale"]),
            _ffn(m))


def _round8(x, lower):
    if lower is None:
        return x
    if lower != "fp8":
        raise ValueError(f"no reference precision {lower!r}")
    s = jnp.maximum(jnp.max(jnp.abs(x), axis=-1, keepdims=True), 1e-30) / 448.0
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def _rope(x, theta):
    """x [B, S, H, D] at positions 0..S-1."""
    d = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _lin(x, w, lower):
    return jnp.einsum("bsk,kn->bsn", _round8(x, lower), w, precision=HI)


@functools.partial(jax.jit, static_argnums=(2,))
def _embed(key, tokens, st):
    _, d, _, _, _, _, vocab = st[:7]
    e = W.table(W.leaf_key(key, "embed"), vocab, d, 1.0)
    return e[tokens].astype(jnp.float32)


@functools.partial(jax.jit, static_argnums=(3, 4))
def _layer(x, key, layer, st, lower):
    (_, d, h, hk, dh, f, _, eps, theta, wp, wf, ffn) = st
    wp = {"format": wp[0], "group": wp[1], "scale": wp[2]}
    wf = {"format": wf[0], "group": wf[1], "scale": wf[2]}
    lin = lambda name, k, n, fmt: W.dense_weight(  # noqa: E731
        W.leaf_key(key, "layers/" + name), layer, k, n, fmt, BENCH_DIR)
    b, s, _ = x.shape
    a = _rms(x, W.vector(W.leaf_key(key, "layers/ln1/g"), layer, d), eps)
    q = _lin(a, lin("attn/wq", d, h * dh, wp), lower).reshape(b, s, h, dh)
    k = _lin(a, lin("attn/wk", d, hk * dh, wp), lower).reshape(b, s, hk, dh)
    v = _lin(a, lin("attn/wv", d, hk * dh, wp), lower).reshape(b, s, hk, dh)
    q, k = _rope(q, theta), _rope(k, theta)
    q, k, v = _round8(q, lower), _round8(k, lower), _round8(v, lower)
    qg = q.reshape(b, s, hk, h // hk, dh) / np.sqrt(dh)
    sc = jnp.einsum("bqhrd,bkhd->bhrqk", qg, k, precision=HI)
    causal = jnp.tril(jnp.ones((s, s), bool))
    p = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), axis=-1)
    o = jnp.einsum("bhrqk,bkhd->bqhrd", p, v, precision=HI)
    x = x + _lin(o.reshape(b, s, h * dh), lin("attn/wo", h * dh, d, wp),
                 lower)
    a = _rms(x, W.vector(W.leaf_key(key, "layers/ln2/g"), layer, d), eps)
    if ffn == "plain":
        hid = jax.nn.gelu(_lin(a, lin("ffn/w_in", d, f, wf), lower),
                          approximate=True)
        return x + _lin(hid, lin("ffn/w_out", f, d, wf), lower)
    g = _lin(a, lin("ffn/w_gate", d, f, wf), lower)
    u = _lin(a, lin("ffn/w_up", d, f, wf), lower)
    hid = g * jax.nn.sigmoid(g) * u
    return x + _lin(hid, lin("ffn/w_down", f, d, wf), lower)


@functools.partial(jax.jit, static_argnums=(3, 4))
def _gaps(x, key, targets, st, lower):
    """Per position: max logit minus the logit of ``targets`` under the
    reference, and the argmax of these logits."""
    _, d, _, _, _, _, vocab, eps = st[:8]
    a = _rms(x, W.vector(W.leaf_key(key, "ln_f/g"), None, d), eps)
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
