"""A temporary copy of the benchmark with a small cell added, for the CPU.

``make_copy`` copies the benchmark's directory and ``BENCHMARK.json`` into a
temporary root, adds the files under ``tests/bench/data`` (a cut-down
granite configuration and a traffic mix) with a limits file and one cell
entry per (config, traffic) pair; no file of the repository is edited.
"""
from __future__ import annotations

import json
import os
import shutil
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
BENCH = os.path.join(ROOT, "benchmarks", "chip")
DATA = os.path.join(HERE, "data")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)


def make_copy(tmp, cells, limit=10.0):
    """``cells``: [(config, traffic)]; returns (root, bench_dir, names)."""
    root = os.path.join(str(tmp), "checkout")
    bench = os.path.join(root, "benchmarks", "chip")
    shutil.copytree(BENCH, bench, ignore=shutil.ignore_patterns(
        "testdata", "__pycache__"))
    for kind in ("configs", "traffic"):
        for name in os.listdir(os.path.join(DATA, kind)):
            shutil.copy(os.path.join(DATA, kind, name),
                        os.path.join(bench, kind, name))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bm = json.load(f)
    names = []
    for config, traffic in cells:
        name = f"{config}.{traffic}"
        names.append(name)
        bm["workloads"].append({"name": name, "config": config,
                                "traffic": traffic, "chips": 1,
                                "why": "CPU test cell"})
        with open(os.path.join(bench, "limits", name + ".json"), "w") as f:
            json.dump({"rows": 64, "mean_logit_gap": {"limit": limit}}, f)
        # report what the batch-decode cell reports
        for m in bm["end_to_end"] + bm["per_layer"]:
            if "granite-8b.batch-decode" in m.get("workloads", ()):
                m["workloads"].append(name)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bm, f, indent=1)
    return root, bench, names
