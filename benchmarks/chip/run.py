"""Run one cell of the benchmark once, on the machine it is started on.

    python benchmarks/chip/run.py --workload granite-8b.batch-decode \
        --seed 7 --seconds 20 --trace 0

The cell (``BENCHMARK.json``'s ``workloads``) names a configuration and a
traffic mix, each a file of its own under this directory.  With
``--trace 0`` the run reports the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics, read from a profiler trace of the
window.  Either way the served tokens are compared with the plain reference
afterwards, and the numbers compared are printed beside their limits.  The
last line of standard output is one JSON object (``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, ...).

It measures nothing off a TPU: without one, or with fewer chips than the
cell asks for, it exits non-zero and prints no result.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

T_START = time.perf_counter()
HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        from chipbench import spec
        cell = spec.workload(args.workload)
        spec.add_program_path()
        import jax
        import repro  # noqa: F401  (the program under test)
    except (ImportError, OSError, KeyError) as e:
        print(f"run.py: cannot set up {args.workload!r}: {e!r}",
              file=sys.stderr)
        return 2
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell["chips"]:
        print(f"run.py: cell {args.workload} needs {cell['chips']} TPU "
              f"chip(s); JAX found {len(devices)} {devices[0].platform} "
              "device(s). Nothing is measured off the chip.",
              file=sys.stderr)
        return 1
    spec.use_compile_cache()
    from chipbench.cell import run_cell
    result, _ = run_cell(args.workload, args.seed, args.seconds,
                         bool(args.trace), t_start=T_START)
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
