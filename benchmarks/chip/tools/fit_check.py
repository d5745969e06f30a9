"""Compile a configuration's serving steps for a described TPU v5e chip
(no chip needed) and print each step's memory analysis.

    JAX_PLATFORMS=cpu python benchmarks/chip/tools/fit_check.py \
        --config granite-8b --n-slots 32 --max-len 1280

The weights and the KV pool are passed as shapes, so nothing is allocated;
the printed bytes are what the chip's compiler plans for one step program
(arguments, outputs, temporaries).  A pool geometry fits when the largest
step's argument + temporary bytes stay under the chip's 16 GB with room for
the process's other buffers.
"""
from __future__ import annotations

import argparse
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--n-slots", type=int, required=True)
    ap.add_argument("--max-len", type=int, required=True)
    ap.add_argument("--bursts", default="1,8")
    args = ap.parse_args(argv)

    from chipbench import spec
    spec.add_program_path()
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    from repro.models import transformer as T
    from repro.models.common import AbstractMaker
    from repro.serve import ServingEngine

    cfg_file = spec.load_config(args.config)
    cfg = spec.model_config(cfg_file)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    dev = SingleDeviceSharding(topo.devices[0])

    def sds(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=dev),
            tree)

    params = sds(T.build_params(cfg, AbstractMaker()))
    scfg = spec.serve_config(cfg_file, max_len=args.max_len,
                             n_slots=args.n_slots)
    engine = ServingEngine(cfg, params, scfg)
    cap = -(-args.max_len // scfg.prefill_chunk) * scfg.prefill_chunk
    cache = sds(T.init_cache(cfg, args.n_slots, cap, abstract=True,
                             kv_dtype=scfg.kv_dtype))
    n = args.n_slots
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=dev)  # noqa
    progs = {
        "prefill_chunk": engine._prefill_chunk.lower(
            params, i32(1, scfg.prefill_chunk), cache, i32(), i32(), True),
        "decode_slots": engine._decode_slots.lower(
            params, i32(n, 1), cache, i32(n),
            jax.ShapeDtypeStruct((n, 2), jnp.uint32, sharding=dev),
            jax.ShapeDtypeStruct((n,), jnp.float32, sharding=dev)),
    }
    for k in [int(x) for x in args.bursts.split(",") if int(x) > 1]:
        progs[f"decode_burst_k{k}"] = engine._decode_burst.lower(
            params, cache, i32(n), i32(n),
            jax.ShapeDtypeStruct((n,), jnp.bool_, sharding=dev), i32(n),
            jax.ShapeDtypeStruct((k, n, 2), jnp.uint32, sharding=dev),
            jax.ShapeDtypeStruct((n,), jnp.float32, sharding=dev), i32(n),
            i32())
    for name, low in progs.items():
        m = low.compile().memory_analysis()
        print(f"{name}: arguments {m.argument_size_in_bytes} "
              f"outputs {m.output_size_in_bytes} alias "
              f"{m.alias_size_in_bytes} temporaries {m.temp_size_in_bytes} "
              f"total {m.argument_size_in_bytes + m.temp_size_in_bytes}",
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
