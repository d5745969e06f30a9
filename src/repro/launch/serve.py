"""Serving driver: load (or synthesize) a mixed-precision checkpoint and
run batched generation — the end-to-end consumer of the paper's technique.

Mesh-aware (DESIGN.md §10): ``--dp``/``--tp`` shard the engine across a
``data x model`` device mesh — packed weights along N on the model axis,
the KV pool slots on the data axis.  On a CPU-only box, validate the
sharded path with forced host devices:

  PYTHONPATH=src python -m repro.launch.serve --arch granite-8b --smoke \
      --dp 2 --tp 4 --force-host-devices 8 --kv-dtype int8

Reports compile time and steady-state tok/s separately: the first
generation pays the XLA compile, so a warmup pass runs the same jitted
step shapes off the clock before the timed run.
"""
from __future__ import annotations

import argparse
import json
import time

from repro.launch.cli import (enable_compile_cache, force_host_devices,
                              serving_mesh)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--kv-dtype", default="bf16",
                    help="KV pool storage: bf16 | int8 | fp8 (DESIGN.md §9; "
                         "legacy adapter — the canonical knob is --policy)")
    ap.add_argument("--policy", default=None, metavar="POLICY_JSON",
                    help="path to a PrecisionPolicy JSON (DESIGN.md §12): "
                         "weight-scheme patterns, KV tier and kernel mode "
                         "as one artifact; overrides --kv-dtype")
    ap.add_argument("--max-burst", type=int, default=8,
                    help="device-resident decode burst cap: K tokens per "
                         "jit dispatch / host sync (1 = per-token dispatch, "
                         "DESIGN.md §11)")
    ap.add_argument("--dp", type=int, default=1,
                    help="data-parallel mesh axis (pool slots shard here)")
    ap.add_argument("--tp", type=int, default=1,
                    help="model-parallel mesh axis (weights/heads shard here)")
    ap.add_argument("--force-host-devices", type=int, default=0,
                    help="CPU validation: fake this many host devices "
                         "(sets XLA_FLAGS before jax initializes)")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="write a Chrome trace-event JSON of the timed run "
                         "(per-request spans + per-dispatch events; open in "
                         "Perfetto / chrome://tracing — DESIGN.md §13)")
    ap.add_argument("--metrics-out", default=None, metavar="PATH",
                    help="write the metrics registry: Prometheus-style text "
                         "exposition at PATH plus periodic JSONL snapshots "
                         "at PATH.jsonl")
    args = ap.parse_args()

    force_host_devices(args.force_host_devices)

    # jax (and everything that initializes it) imports AFTER the XLA_FLAGS
    # setup above — device counts are fixed at backend initialization
    import jax
    import numpy as np

    enable_compile_cache()
    from repro.configs import get_config
    from repro.models.common import QuantMaker
    from repro.models import transformer as T
    from repro.serve import ServeConfig, ServingEngine

    from repro.quant.policy import PrecisionPolicy

    cfg = get_config(args.arch, smoke=args.smoke)
    mesh = serving_mesh(args.dp, args.tp)
    if mesh is not None:
        print(f"mesh: dp={args.dp} x tp={args.tp} over "
              f"{jax.devices()[0].platform}")

    if args.policy:
        with open(args.policy) as f:
            policy = PrecisionPolicy.from_json(f.read())
    else:
        # legacy flags keep working as a thin adapter: they emit the
        # equivalent policy (printed below so the flag set is migratable)
        policy = PrecisionPolicy.from_legacy(kv_dtype=args.kv_dtype)

    print(f"building {cfg.name} with quantized weights "
          f"(proj={cfg.scheme_proj}, ffn={cfg.scheme_ffn})")
    params = T.build_params(cfg, QuantMaker(jax.random.PRNGKey(args.seed)))
    engine = ServingEngine(cfg, params, ServeConfig(
        max_len=args.prompt_len + args.max_new,
        temperature=args.temperature, policy=policy,
        max_burst=args.max_burst, mesh=mesh))
    print(f"precision policy: {engine.policy.to_json()}")

    rng = np.random.default_rng(args.seed)
    batch = {"tokens": rng.integers(
        1, cfg.vocab, (args.batch, args.prompt_len)).astype(np.int32)}
    if cfg.family == "vlm":
        import jax.numpy as jnp
        batch["patches"] = jnp.full((args.batch, cfg.n_patches, cfg.d_model),
                                    0.02, jnp.bfloat16)
    elif cfg.family == "audio":
        import jax.numpy as jnp
        batch["frames"] = jnp.full((args.batch, cfg.n_frames, cfg.d_model),
                                   0.02, jnp.bfloat16)

    # observability bundle for the timed run (DESIGN.md §13): tracing and
    # registry + snapshots.  Only built when a sink was requested —
    # otherwise the scheduler runs its zero-overhead disabled path.
    obs = None
    if args.trace or args.metrics_out:
        from repro.obs import (MetricsRegistry, Observability,
                               SnapshotWriter, Tracer)
        registry = MetricsRegistry() if args.metrics_out else None
        obs = Observability(
            tracer=Tracer() if args.trace else None,
            registry=registry,
            snapshots=SnapshotWriter(registry, args.metrics_out + ".jsonl")
            if registry is not None else None)

    # warmup: one full-shape generation compiles every jit off the clock.
    # Scheduler families compile chunk/decode/sample once regardless of
    # batch, but the legacy static-batch loop (ssm/hybrid/audio/vlm) sizes
    # its cache from (batch, prompt+max_new) — warming up with the real
    # shapes makes the timed run steady-state for every family.
    # perf_counter, not time.time(): wall deltas must be monotonic and
    # high-resolution (time.time() can step under NTP and ticks coarsely
    # on some hosts, which corrupts sub-second compile/steady windows)
    t0 = time.perf_counter()
    engine.generate(batch, max_new_tokens=args.max_new, seed=args.seed)
    compile_s = time.perf_counter() - t0
    print(f"warmup (compile + first run) {compile_s:.2f}s")

    t0 = time.perf_counter()
    out = engine.generate(batch, max_new_tokens=args.max_new, seed=args.seed,
                          obs=obs)
    dt = time.perf_counter() - t0
    new_tokens = int(out["lengths"].sum())
    print(f"generated {out['generated'].shape} in {dt:.2f}s "
          f"({new_tokens / dt:.1f} tok/s steady-state)")
    print("first rows:", out["generated"][:2, :8].tolist())
    report = {
        "batch": out["batch"], "prompt_len": out["prompt_len"],
        "new_tokens": new_tokens, "kv_dtype": engine.scfg.kv_dtype,
        "policy": engine.policy.to_dict(),
        "topology": engine.topology,
        "compile_s": round(compile_s, 2), "wall_s": round(dt, 2),
        "steady_tok_s": round(new_tokens / dt, 1)}
    if "decode_dispatches" in out:   # scheduler families: burst accounting
        report.update({
            "max_burst": args.max_burst,
            "decode_dispatches": out["decode_dispatches"],
            "decode_dispatches_per_token": round(
                out["decode_dispatches"] / max(new_tokens, 1), 4),
            "host_syncs": out["host_syncs"],
            "burst_hist": {str(k): v for k, v
                           in sorted(out["burst_hist"].items())}})
    if obs is not None:
        if obs.tracer is not None and len(obs.tracer):
            obs.tracer.write(args.trace)
            print(f"trace: {args.trace} ({len(obs.tracer)} events)")
        if obs.registry is not None:
            with open(args.metrics_out, "w") as f:
                f.write(obs.registry.expose())
            snaps = obs.snapshots.n_written if obs.snapshots else 0
            print(f"metrics: {args.metrics_out} "
                  f"(+{snaps} snapshots in {args.metrics_out}.jsonl)")
    print(json.dumps(report, allow_nan=False))


if __name__ == "__main__":
    main()
