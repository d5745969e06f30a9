"""Find a cell's configuration, traffic mix, limits and readers by name.

Everything specific to one configuration, traffic mix, per-layer metric or
cell lives in a file of its own under the benchmark's directory:

    configs/<config>.json        model sizes, weight formats, pool geometry
    traffic/<mix>.json           parameters of the traffic generator
    limits/<workload>.json       the correctness limit of one cell
    layer_metrics/<metric>.py    one reader per per-layer metric
    references/<name>.py         the plain reference a configuration names:
                                 its forward pass, its linears, and rules
                                 for any leaf the harness does not draw
    formats/<format>.py          one weight format: the program's leaves,
                                 the reference's floats, the bytes a read
                                 costs

``BENCHMARK.json`` at the checkout's root names the cells; this module only
looks files up by those names, so a new cell is new files and entries.
"""
from __future__ import annotations

import importlib.util
import json
import os
import sys
from typing import Any, Dict, Optional

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(os.path.dirname(BENCH_DIR))


def add_program_path(root: str = ROOT) -> None:
    """Put the program under test (``src/``) on the import path."""
    src = os.path.join(root, "src")
    if src not in sys.path:
        sys.path.insert(0, src)


def use_compile_cache(root: str = ROOT) -> None:
    """JAX's persistent compilation cache at ``<checkout>/.jax_cache``, for
    every program however small: a fixed path (the path is part of an
    entry's key) inside the checkout, whatever the environment names, so
    that only a checkout's first run compiles."""
    import jax
    jax.config.update("jax_compilation_cache_dir",
                      os.path.join(root, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def _json(path: str) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def benchmark(root: str = ROOT) -> Dict[str, Any]:
    return _json(os.path.join(root, "BENCHMARK.json"))


def workload(name: str, root: str = ROOT) -> Dict[str, Any]:
    for w in benchmark(root)["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")


def metrics_for(name: str, kind: str, root: str = ROOT):
    """The ``end_to_end`` or ``per_layer`` entries that cell ``name``
    reports: those that list it, and those that list no cells."""
    return [m for m in benchmark(root)[kind]
            if "workloads" not in m or name in m["workloads"]]


def load_config(name: str, bench_dir: str = BENCH_DIR) -> Dict[str, Any]:
    cfg = _json(os.path.join(bench_dir, "configs", name + ".json"))
    if cfg["name"] != name:
        raise ValueError(f"configs/{name}.json names {cfg['name']!r}")
    return cfg


def load_traffic(name: str, bench_dir: str = BENCH_DIR) -> Dict[str, Any]:
    t = _json(os.path.join(bench_dir, "traffic", name + ".json"))
    t.setdefault("name", name)
    return t


def load_limits(name: str, bench_dir: str = BENCH_DIR) -> Dict[str, Any]:
    return _json(os.path.join(bench_dir, "limits", name + ".json"))


_MODULES: Dict[str, Any] = {}


def load_module(kind: str, name: str, bench_dir: str = BENCH_DIR):
    """Import ``<bench_dir>/<kind>/<name>.py`` (names may hold dots), once
    per process and file; None when there is no such file."""
    path = os.path.realpath(os.path.join(bench_dir, kind, name + ".py"))
    if path in _MODULES:
        return _MODULES[path]
    if not os.path.exists(path):
        return None
    spec = importlib.util.spec_from_file_location(
        f"chipbench_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    _MODULES[path] = mod
    return mod


def layer_format(w: Dict[str, Any], k: int) -> Dict[str, Any]:
    """The weight format ``w`` as it applies to a linear of ``k`` inputs:
    one scale per ``group`` inputs, or one per column where the file
    states -1 or the linear has fewer inputs than a group, as the program
    holds them (``repro.quant.schemes.effective_group``)."""
    g = w["group"]
    return dict(w, group=k if g == -1 or g > k else g)


def model_config(cfg_file: Dict[str, Any]):
    """The program's ``ModelConfig`` built from the file's ``model`` block
    (the sizes as they run) and its weight schemes."""
    from repro.models.transformer import ModelConfig
    m = cfg_file["model"]
    kw = {k: v for k, v in m.items() if k not in ("norm_eps",)}
    w = cfg_file["weights"]
    return ModelConfig(name=cfg_file["name"], scheme_proj=w["proj"]["scheme"],
                       scheme_ffn=w["ffn"]["scheme"], **kw)


def serve_config(cfg_file: Dict[str, Any], *, max_len: int,
                 n_slots: Optional[int] = None):
    """The program's ``ServeConfig`` for the cell: only what defines the
    deployment is pinned (the KV memory budget, from which the program
    derives the slot count at ``max_len``; the KV tier; slab or paged
    pool; no EOS); every tuning knob keeps the program's default.
    ``n_slots`` overrides the budget (the fit check sizes slots directly)."""
    from repro.serve import ServeConfig
    s = cfg_file["serve"]
    budget = None if n_slots else s["kv_budget_bytes"]
    return ServeConfig(max_len=max_len, n_slots=n_slots or 8,
                       cache_budget_bytes=budget, kv_dtype=s["kv"],
                       paged=s["paged"], eos_id=-1)
