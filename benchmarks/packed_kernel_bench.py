"""Chip timing of one quantized weight product: the jnp dequantize-and-dot
path against the packed Pallas kernel, at several row counts.

    python benchmarks/packed_kernel_bench.py --shape 4096,14336 \
        --rows 16,64,256 [--scheme awq_int4] [--layers 16] [--unstacked]

``--layers`` weights of one shape are stacked [L, K / per, N] and run in a
``lax.scan``, as the model's layer scan feeds its products; ``--unstacked``
also runs them as separate arrays, which shows what slicing a stacked
weight costs the kernel.  Each (rows, path) prints one JSON line: device
milliseconds per product (median of ``--iters`` timed calls, each ending
in ``block_until_ready``), the packed words and scales streamed per second,
the kernel's block plan, and the largest difference between the two paths
over the largest output.  ``--src`` imports the program from another
checkout's ``src`` (a parent commit, to time its kernel).  Timings are
taken on the chip: off a TPU the tool exits 1.
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _timed(fn, args, iters: int) -> float:
    """Median seconds of one call, after two untimed ones."""
    for _ in range(2):
        fn(*args).block_until_ready()
    times = []
    for _ in range(iters):
        t = time.perf_counter()
        fn(*args).block_until_ready()
        times.append(time.perf_counter() - t)
    return statistics.median(times)


def bench(scheme_name: str, k: int, n: int, rows, *, layers: int,
          iters: int, paths, unstacked: bool, seed: int = 0):
    """One JSON-ready dict per (rows, path, stacked or not)."""
    import jax
    import jax.numpy as jnp
    from repro.kernels import packed_matmul as pm
    from repro.kernels.ops import quantized_matmul
    from repro.quant.schemes import (QuantizedLinearWeights,
                                     effective_group, get_scheme)

    scheme = get_scheme(scheme_name)
    per = 32 // scheme.weight_bits
    group = effective_group(scheme.group_size, k)
    kp, ks, kx = jax.random.split(jax.random.PRNGKey(seed), 3)
    # any bit pattern is a code of the scheme (fp8's NaN codes decode as 0)
    packed = jax.random.bits(kp, (layers, k // per, n), jnp.uint32) \
        .view(jnp.int32)
    scales = jax.random.uniform(ks, (layers, k // group, n), jnp.float32,
                                0.005, 0.02)
    weight_bytes = (packed[0].size + scales[0].size) * 4

    def product(x, p, s, kernel):
        qw = QuantizedLinearWeights(scheme, p, s, (k, n))
        return quantized_matmul(x, qw, use_kernel=kernel,
                                out_dtype=jnp.float32)

    def stacked(kernel):
        def run(x, packed, scales):
            def body(acc, ps):
                return acc + product(x, *ps, kernel), None
            acc0 = jnp.zeros((x.shape[0], n), jnp.float32)
            return jax.lax.scan(body, acc0, (packed, scales))[0]
        return jax.jit(run)

    def split(kernel):
        def run(x, ps, ss):
            acc = jnp.zeros((x.shape[0], n), jnp.float32)
            for p, s in zip(ps, ss):
                acc = acc + product(x, p, s, kernel)
            return acc
        return jax.jit(run)

    parts = (list(packed), list(scales)) if unstacked else None
    out = []
    for m in rows:
        x = jax.random.normal(jax.random.fold_in(kx, m), (m, k), jnp.bfloat16)
        one = {p: jax.jit(lambda x, a, b, f=(p == "pallas"): product(
            x, a, b, f))(x, packed[0], scales[0]) for p in paths}
        ref = one.get("jnp", one[paths[0]])
        plan = (pm.packed_block_plan(m, k, n, scheme)
                if hasattr(pm, "packed_block_plan") else None)
        modes = [("stacked", stacked, (packed, scales))]
        if unstacked:
            modes.append(("unstacked", split, parts))
        for path in paths:
            gap = float(jnp.max(jnp.abs(one[path] - ref))
                        / jnp.max(jnp.abs(ref)))
            for mode, make, args in modes:
                t0 = time.perf_counter()
                fn = make(path == "pallas")
                fn.lower(x, *args).compile()
                compile_s = time.perf_counter() - t0
                sec = _timed(fn, (x, *args), iters) / layers
                out.append({
                    "scheme": scheme_name, "k": k, "n": n, "rows": m,
                    "path": path, "weights": mode, "ms": 1e3 * sec,
                    "weight_gb_per_s": weight_bytes / sec / 1e9,
                    "plan": plan if path == "pallas" else None,
                    "max_gap_vs_jnp": gap, "compile_s": compile_s})
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--scheme", default="awq_int4")
    ap.add_argument("--shape", default="4096,14336", help="K,N")
    ap.add_argument("--rows", default="16,64,256")
    ap.add_argument("--layers", type=int, default=16)
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--paths", default="jnp,pallas")
    ap.add_argument("--unstacked", action="store_true")
    ap.add_argument("--src", default=ROOT,
                    help="checkout whose src/ holds the program")
    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.join(os.path.abspath(args.src), "src"))
    import jax
    if jax.devices()[0].platform != "tpu":
        print("packed_kernel_bench.py: no TPU; timings are taken on the chip",
              file=sys.stderr)
        return 1
    k, n = (int(v) for v in args.shape.split(","))
    for rec in bench(args.scheme, k, n,
                     [int(v) for v in args.rows.split(",")],
                     layers=args.layers, iters=args.iters,
                     paths=args.paths.split(","), unstacked=args.unstacked):
        rec["device"] = jax.devices()[0].device_kind
        print(json.dumps(rec), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
