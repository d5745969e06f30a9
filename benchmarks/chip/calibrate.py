"""Readings for a cell's correctness limit: the program, its control, faults.

    python benchmarks/chip/calibrate.py --workload granite-8b.batch-decode \
        --seeds 101,102,103 --seconds 30 --ref-control fp8
    python benchmarks/chip/calibrate.py --workload granite-8b.batch-decode \
        --seeds 101,102,103 --seconds 30 --fault half_batch

For each seed, one run of the cell (as ``run.py`` makes it, untraced) in
this one process, printing one JSON line per seed: the gaps of the served
tokens below the reference's best logit (``program``: mean, widest, share
of tokens off the best), the run's ``correct`` and its checks.  With
``--ref-control``, the same for the token a lower-precision reference puts
first at each of those positions, with the check's verdict on it (``ok``
must be false).  With ``--fault``, the timed path is broken underneath
(``chipbench.faults``) and ``correct`` must come out false.  Benchmark runs
never run this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--ref-control", default=None)
    ap.add_argument("--fault", default=None)
    args = ap.parse_args(argv)
    from chipbench import faults, spec
    spec.add_program_path()
    import jax
    if jax.devices()[0].platform != "tpu":
        print("calibrate.py: no TPU; readings are taken on the chip",
              file=sys.stderr)
        return 1
    spec.use_compile_cache()
    from chipbench.cell import run_cell
    controls = (args.ref_control,) if args.ref_control else ()
    mutate = faults.FAULTS[args.fault] if args.fault else None
    for seed in [int(s) for s in args.seeds.split(",")]:
        r, _ = run_cell(args.workload, seed, args.seconds, False,
                        controls=controls, mutate=mutate)
        print(json.dumps({"seed": seed, "fault": args.fault,
                          "correct": r["correct"],
                          "program": r.get("gaps", {}),
                          "controls": r.get("controls", {}),
                          "checks": r["checks"],
                          "metrics": r["metrics"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
