"""Weight formats and architectures come from files found by name.

The int4 rules (``formats/int4.py``) draw what the harness drew before
they moved there: checksums of granite-smoke's whole parameter tree and of
some of the reference's weights, computed at commit 4f7f590 (before the
move) and written here.  A format module or a reference module placed in a
temporary copy of the benchmark is used for drawing, for the reference and
for counting, with no file of the repository edited.
"""
import hashlib
import json
import os

import jax
import numpy as np
import pytest

import smoke_cell
from chipbench import costs, spec, weights
from chipbench.cell import run_cell

SEED = 2**31 + 12345
DATA_CONFIGS = os.path.join(smoke_cell.DATA, "configs")

# sha256 of each leaf's bytes, first 16 hex digits (commit 4f7f590)
PINNED_TREE = {
    "embed": "2062fbedd6cd6226",
    "layers/attn/wk/0": "a53a1b199ad8b68c",
    "layers/attn/wk/1": "432767861623063c",
    "layers/attn/wo/0": "cd6a694bb8845ef7",
    "layers/attn/wo/1": "c9490b3395e669dd",
    "layers/attn/wq/0": "0beb9442a2fdb787",
    "layers/attn/wq/1": "8825b3caa1b6f65f",
    "layers/attn/wv/0": "0ffc0d18e88dadad",
    "layers/attn/wv/1": "ceda41f80022e09c",
    "layers/ffn/w_down/0": "e3b919d45fd5825f",
    "layers/ffn/w_down/1": "e2ed38bb69ef20a7",
    "layers/ffn/w_gate/0": "29d6ce916fdb47d3",
    "layers/ffn/w_gate/1": "6ba3dfbb4d53c66e",
    "layers/ffn/w_up/0": "846b4d749b73f55c",
    "layers/ffn/w_up/1": "28053fba6b481044",
    "layers/ln1/g": "6d9a4434fceb4051",
    "layers/ln2/g": "6d89ebed08b4bc8d",
    "lm_head": "996851ee1b870359",
    "ln_f/g": "c98ca95507b6bfd3",
}
# the reference's f32 weights: (linear, K, N, weight kind, layer)
PINNED_DENSE = {
    ("attn/wq", 128, 128, "proj", 0): "18e2917e8474964a",
    ("attn/wq", 128, 128, "proj", 1): "07c7c959a324dc26",
    ("ffn/w_down", 256, 128, "ffn", 0): "ea5e18622b3a1e89",
    ("ffn/w_down", 256, 128, "ffn", 1): "d0f12a404e1e4fca",
}


def _sha(x) -> str:
    return hashlib.sha256(np.asarray(x).tobytes()).hexdigest()[:16]


def _config(name):
    with open(os.path.join(DATA_CONFIGS, name + ".json")) as f:
        return json.load(f)


def test_int4_draws_are_pinned():
    cfg_file = _config("granite-smoke")
    params = weights.make_params(cfg_file, spec.model_config(cfg_file), SEED)
    flat, _ = jax.tree_util.tree_flatten_with_path(params)
    assert {weights._path(kp): _sha(leaf) for kp, leaf in flat} == \
        PINNED_TREE
    key = weights.base_key(SEED)
    got = {(name, k, n, kind, layer): _sha(weights.dense_weight(
        weights.leaf_key(key, "layers/" + name), layer, k, n,
        cfg_file["weights"][kind]))
        for name, k, n, kind, layer in PINNED_DENSE}
    assert got == PINNED_DENSE


def test_plain_ffn_is_counted_by_hand():
    """starcoder2-smoke: q, o 64 x 64; k, v 64 x 32; w_in 64 x 128, w_out
    128 x 64; int4 codes, one f32 scale per column where a linear has 64
    inputs, per 128 inputs of w_out."""
    cfg = _config("starcoder2-smoke")
    per_layer = (2 * 64 * 64 + 2 * 64 * 32 + 2 * 64 * 128) // 2 \
        + (64 + 32 + 32 + 64 + 128) * 4 + 64 * 4
    want = 2 * per_layer + 64 * 512 * 2 + (2 * 2 + 1) * 64 * 4
    assert costs.weight_bytes(cfg) == want
    assert costs.weight_flops_per_token(cfg, with_head=False) == \
        2.0 * 2 * (2 * 64 * 64 + 2 * 64 * 32 + 2 * 64 * 128)


# int4 codes with power-of-two scales counted at one byte each: a format
# no file of the repository has, built on the int4 rules
POW2 = '''
import os

import jax
import jax.numpy as jnp

from chipbench import spec

int4 = spec.load_module(
    "formats", "int4",
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CALLS = []


def check(w):
    CALLS.append("check")


def _scales(key, k, n, w):
    s = int4.draw_scales(jax.random.fold_in(key, 1), k, n, w["group"])
    return jnp.exp2(jnp.round(jnp.log2(s)))


def served(key, k, n, w):
    CALLS.append("served")
    return int4.served(key, k, n, w)[0], _scales(key, k, n, w)


def dense(key, k, n, w):
    CALLS.append("dense")
    codes = int4.draw_codes(jax.random.fold_in(key, 0), k, n)
    vals = jnp.where(codes >= 8, codes - 16, codes).astype(jnp.float32)
    g = w["group"]
    return (vals.reshape(k // g, g, n)
            * _scales(key, k, n, w)[:, None, :]).reshape(k, n)


def nbytes(k, n, w):
    CALLS.append("nbytes")
    return k * n // 2 + k // w["group"] * n
'''


def test_format_module_in_a_copy(tmp_path):
    """A configuration naming a format that only the copy has is drawn,
    decoded for the reference and counted through that format's module."""
    assert not os.path.exists(os.path.join(smoke_cell.BENCH, "formats",
                                           "int4-pow2.py"))
    root, bench, names = smoke_cell.make_copy(
        tmp_path, [("granite-pow2", "smoke-closed")], limit=0.001)
    with open(os.path.join(bench, "formats", "int4-pow2.py"), "w") as f:
        f.write(POW2)
    cfg = _config("granite-smoke")
    cfg["name"] = "granite-pow2"
    cfg["weights"]["ffn"]["format"] = "int4-pow2"
    with open(os.path.join(bench, "configs", "granite-pow2.json"), "w") as f:
        json.dump(cfg, f)
    r, _ = run_cell(names[0], SEED, 1.0, False, root=root, bench_dir=bench)
    assert r["correct"] is True, r["checks"]
    rules = spec.load_module("formats", "int4-pow2", bench)
    assert {"check", "served", "dense"} <= set(rules.CALLS)
    # the FFN's 98,304 weights of a layer: codes at 4 bits, one byte a
    # scale per 128 inputs; the projections stay int4 with f32 scales
    ffn = 98_304 // 2 + 98_304 // 128
    proj = 49_152 // 2 + 49_152 // 128 * 4
    assert costs.weight_bytes(cfg, bench) == \
        2 * (proj + ffn) + 128 * 512 * 2 + 5 * 128 * 4
    assert "nbytes" in rules.CALLS


# a reference that draws LayerNorm biases: a leaf the harness has no rule for
DRAW_LEAF = '''
import jax.numpy as jnp


def draw_leaf(path, shape, dtype, key):
    if not path.endswith("/b"):
        raise ValueError(f"no rule to draw {path}")
    return jnp.full(shape, 0.25, dtype)
'''


def test_reference_draws_the_leaves_it_names(tmp_path):
    root, bench, _ = smoke_cell.make_copy(tmp_path, [])
    with open(os.path.join(bench, "references", "dense_ln.py"), "w") as f:
        f.write(DRAW_LEAF)
    cfg_file = _config("granite-smoke")
    cfg_file["model"]["norm"] = "layer"
    cfg = spec.model_config(cfg_file)
    with pytest.raises(ValueError, match="layers/ln1/b"):
        weights.make_params(cfg_file, cfg, SEED, bench)
    cfg_file["reference"] = "dense_ln"
    params = weights.make_params(cfg_file, cfg, SEED, bench)
    assert np.all(np.asarray(params["layers"]["ln1"]["b"]) == 0.25)
    assert np.all(np.asarray(params["ln_f"]["b"]) == 0.25)
    assert _sha(params["embed"]) == PINNED_TREE["embed"]


def test_module_lookup_keeps_found_files_only(tmp_path):
    """A file written after a lookup missed it is found; one file is one
    module, however its directory is spelled."""
    os.makedirs(tmp_path / "formats")
    assert spec.load_module("formats", "late", str(tmp_path)) is None
    (tmp_path / "formats" / "late.py").write_text("X = 1\n")
    mod = spec.load_module("formats", "late", str(tmp_path))
    assert mod.X == 1
    assert spec.load_module("formats", "late", str(tmp_path) + "/.") is mod
