"""One run of a cell with the program's scopes and spans read from its trace.

    python benchmarks/chip/tools/scope_split.py \
        --workload granite-8b.batch-decode --seeds 7,8 --seconds 30 --trace 1

Each seed is one run of the cell as ``run.py`` makes it, in this one
process; ``--keep-trace DIR`` copies a traced run's trace file there.
Every run prints one JSON line: output tokens/s and rounds/s of the window
(traced or not, so the cost of tracing shows), the per-layer readings, the
correctness checks and, traced, the trace's breakdown and the whole
reduction by the program's scopes and spans (``chipbench.scopes``).
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))


def run(workload: str, seed: int, seconds: float, trace: bool, *,
        keep: str = None):
    """One run; returns the JSON object printed for it."""
    from chipbench import cell, records
    trace_dir = os.path.join(keep, f"{workload}.{seed}") \
        if keep and trace else None
    if trace_dir:
        os.makedirs(trace_dir)
    result, rec = cell.run_cell(workload, seed, seconds, trace,
                                trace_dir=trace_dir)
    if trace_dir:
        shutil.copy(rec["trace_file"], trace_dir + ".xplane.pb")
        shutil.rmtree(trace_dir)
    out = {"workload": workload, "seed": seed, "trace": trace,
           "correct": result["correct"], "checks": result["checks"],
           "metrics": {k: v["value"] for k, v in result["metrics"].items()},
           "output_tok_s": cell.end_to_end(rec, 0.0)["output_tok_s"],
           "rounds_per_s": len(records.window_steps(rec)) / rec["window_s"],
           "window_s": rec["window_s"]}
    if trace:
        out["breakdown"] = result.get("breakdown")
        out["device"] = result["device"]
        out["scopes"] = rec["scopes"]
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
