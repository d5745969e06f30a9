"""One run of a cell with the program's scopes and spans read from its trace.

    python benchmarks/chip/tools/scope_split.py \
        --workload granite-8b.batch-decode --seeds 7,8 --seconds 30 --trace 1

Each seed is one run of the cell as ``run.py`` makes it, in this one
process.  With ``--trace 1`` the window's profiler trace is also reduced by
``chipbench.scopes`` (device seconds per named scope of the decode
programs, the ``serve.*`` host spans) and the readers of
``layer_metrics/`` that read that reduction are applied to the run's
record; ``--keep-trace DIR`` copies the trace file there first.  Every run
prints one JSON line: output tokens/s and rounds/s of the window (traced
or not, so the cost of tracing shows), the per-layer readings, the
correctness checks and, traced, the trace's breakdown.

``chipbench.cell`` does not call ``scopes.reduce`` itself; until it does,
this tool wraps the run's own calls (the trace reduction, the reader
lookup and the end-to-end arithmetic) to see the same trace and record.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

READERS = ("qmatmul_ms.batch", "qmatmul_roofline.batch",
           "decode_attention_ms.batch", "kv_write_ms.batch",
           "host_step_ms.batch")


def run(workload: str, seed: int, seconds: float, trace: bool, *,
        keep: str = None, root: str = None, bench_dir: str = None):
    """One run; returns the JSON object printed for it."""
    from chipbench import cell, records, scopes, spec
    from chipbench import trace as tr
    root = root or spec.ROOT
    bench_dir = bench_dir or spec.BENCH_DIR
    seen = {}
    reduce, load, end_to_end = tr.reduce, spec.load_module, cell.end_to_end

    def reduce_too(path, *a, **kw):
        if keep:
            os.makedirs(keep, exist_ok=True)
            shutil.copy(path, os.path.join(keep, f"{workload}.{seed}"
                                                 ".xplane.pb"))
        seen["scopes"] = scopes.reduce(path)
        cell.log(scopes.table(seen["scopes"]))
        return reduce(path, *a, **kw)

    class Reader:
        def __init__(self, mod):
            self.mod = mod

        def read(self, rec):
            seen["rec"] = rec
            return self.mod.read(rec)

    def load_seen(kind, name, *a, **kw):
        mod = load(kind, name, *a, **kw)
        return Reader(mod) if kind == "layer_metrics" and mod else mod

    def end_to_end_seen(rec, setup_s):
        seen["rec"] = rec
        return end_to_end(rec, setup_s)

    tr.reduce, spec.load_module, cell.end_to_end = (reduce_too, load_seen,
                                                    end_to_end_seen)
    try:
        result = cell.run_cell(workload, seed, seconds, trace, root=root,
                               bench_dir=bench_dir)
    finally:
        tr.reduce, spec.load_module, cell.end_to_end = (reduce, load,
                                                        end_to_end)
    rec = seen["rec"]
    out = {"workload": workload, "seed": seed, "trace": trace,
           "correct": result["correct"], "checks": result["checks"],
           "metrics": {k: v["value"] for k, v in result["metrics"].items()},
           "output_tok_s": end_to_end(rec, 0.0)["output_tok_s"],
           "rounds_per_s": len(records.window_steps(rec)) / rec["window_s"],
           "window_s": rec["window_s"]}
    if trace:
        out["breakdown"] = result.get("breakdown")
        out["device"] = result["device"]
        rec["scopes"] = seen.get("scopes")
        out["scopes"] = rec["scopes"]
        for name in READERS:
            reader = load("layer_metrics", name, bench_dir)
            out["metrics"][name] = reader.read(rec)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=1)
    ap.add_argument("--keep-trace", default=None)
    args = ap.parse_args(argv)
    from chipbench import spec
    spec.add_program_path()
    import jax
    if jax.devices()[0].platform != "tpu":
        print("scope_split.py: no TPU; readings are taken on the chip",
              file=sys.stderr)
        return 1
    spec.use_compile_cache()
    for seed in [int(s) for s in args.seeds.split(",")]:
        print(json.dumps(run(args.workload, seed, args.seconds,
                             bool(args.trace), keep=args.keep_trace)),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
