"""Smoke run of the serving path on a TPU, at a model's full published width.

    python chip_smoke.py              # one chip:  granite-8b
    python chip_smoke.py --chips 4    # four chips: qwen3-moe-30b-a3b, 1x4 mesh

One process drives the normal entry points — seeded random weights built
by ``QuantMaker``, then ``Scheduler`` -> ``ServingEngine`` -> KV pool ->
Pallas kernels — and checks what comes out:

* one chip: granite-8b (36 layers, awq_int4 projections and FFN) serves
  4 greedy requests (prompts of 128-512 tokens, 32 new tokens each) under
  ``kernel="pallas"`` with a bf16 and an int8 KV tier, then the same
  requests under ``kernel="jnp"``.  The first-step logits of the two
  kernel modes (prefill's last position, and one decode step fed the same
  tokens) must agree within ``LOGIT_TOL``; the compiled decode step must
  hold a Pallas kernel (``tpu_custom_call``); no kernel site may fall back
  to the jnp path.
* ``--chips 4``: qwen3-moe-30b-a3b at full depth on a data=1 x model=4
  mesh serves 4 requests; then the same config cut to ``CUT_LAYERS``
  layers runs meshless on device 0 and on the mesh, and their first-step
  logits must agree within ``LOGIT_TOL``.  Stacked-expert leaves have no
  kernel sharding spec and take the jnp path under the mesh by design; the
  phase prints those sites, and a fallback at any other site fails it.

Every number printed is measured on the chip in this run.  The script
fails — non-zero exit, no result line — when JAX finds no TPU or when any
phase fails.  Its last line is ``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time
import warnings

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

SEED = 0
PROMPT_LENS = (128, 256, 384, 512)
MAX_NEW = 32
CUT_LAYERS = 4
# Largest first-step logit difference between two paths, as a share of the
# reference logits' largest magnitude.  Both paths give the MXU the same
# bf16 dequantized weights, but the jnp path rounds each product's output
# and attends through bf16 einsums, while the Pallas kernels return f32
# products and attend in f32.  Each layer's output therefore differs by
# about one bf16 rounding (2^-8 relative), and the bf16 residual stream
# carries those differences through every layer.
LOGIT_TOL = 0.1


def log(msg: str) -> None:
    print(msg, flush=True)


def requests_for(vocab: int):
    import numpy as np
    rng = np.random.default_rng(SEED)
    return [rng.integers(1, vocab, n).astype(np.int32) for n in PROMPT_LENS]


def build_params(cfg, *, specs=None):
    """Seeded quantized weights, built on the device; (params, seconds)."""
    import jax
    from repro.models import transformer as T
    from repro.models.common import QuantMaker
    t0 = time.perf_counter()
    params = T.build_params(cfg, QuantMaker(jax.random.PRNGKey(SEED),
                                            specs=specs))
    jax.block_until_ready(params)
    return params, time.perf_counter() - t0


def make_engine(cfg, params, kernel: str, *, mesh=None):
    from repro.quant.policy import PrecisionPolicy
    from repro.serve import ServeConfig, ServingEngine
    return ServingEngine(cfg, params, ServeConfig(
        max_len=max(PROMPT_LENS) + MAX_NEW, n_slots=len(PROMPT_LENS),
        policy=PrecisionPolicy(kernel=kernel), mesh=mesh))


def serve(engine, prompts, tier: str):
    """Greedy requests through the continuous-batching scheduler at KV tier
    ``tier``; returns the generated token lists."""
    from repro.serve import Request, SamplingParams, Scheduler
    sched = Scheduler(engine, tiers=[tier])
    reqs = [sched.submit(Request(prompt=p, sampling=SamplingParams(
        max_new_tokens=MAX_NEW))) for p in prompts]
    sched.run()
    assert all(r.finish_reason == "length" for r in reqs), \
        [r.finish_reason for r in reqs]
    return [list(r.output_tokens) for r in reqs]


def first_step_logits(engine, prompts, tier: str):
    """f32 logits of prefill's last position and of one decode step fed
    each prompt's first token: ([R, V], [R, V]) host arrays."""
    import numpy as np
    pool = engine.new_pool(kv_dtype=tier)
    slots = list(range(len(prompts)))
    prefill = np.stack([np.asarray(x, np.float32) for x in
                        engine.prefill_into_slots(pool, slots, prompts)])
    decode = engine.decode_slots_with_logits(
        pool, np.array([p[0] for p in prompts], np.int32))
    return prefill, np.asarray(decode, np.float32)


def decode_step_has_kernel(engine, tier: str) -> bool:
    """Whether the compiled fused decode step holds a Pallas kernel."""
    import jax.numpy as jnp
    pool = engine.new_pool(kv_dtype=tier)
    n = pool.n_slots
    step = engine._steps_for(pool)[1]
    hlo = step.lower(engine.params, jnp.zeros((n, 1), jnp.int32), pool.cache,
                     jnp.asarray(pool.lengths), jnp.zeros((n, 2), jnp.uint32),
                     jnp.zeros((n,), jnp.float32)).compile().as_text()
    return "tpu_custom_call" in hlo


def compare(name: str, got, want) -> None:
    """Largest |got - want| against LOGIT_TOL x max|want|; raises past it."""
    import numpy as np
    assert got.shape == want.shape and np.isfinite(got).all() \
        and np.isfinite(want).all(), name
    diff = float(np.abs(got - want).max())
    scale = float(np.abs(want).max())
    rel_rms = float(np.linalg.norm(got - want) / np.linalg.norm(want))
    log(f"  {name}: max|diff| {diff!r} (limit {LOGIT_TOL * scale!r}, "
        f"ref max|logit| {scale!r}, relative rms {rel_rms!r})")
    if not diff <= LOGIT_TOL * scale:
        raise AssertionError(f"{name}: logits differ by {diff} > "
                             f"{LOGIT_TOL} x {scale}")


def agreement(a, b) -> float:
    """Share of greedy tokens, position by position, that two runs agree on."""
    pairs = [(x, y) for ra, rb in zip(a, b) for x, y in zip(ra, rb)]
    return sum(x == y for x, y in pairs) / len(pairs)


def peak_bytes(device):
    stats = device.memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def run_one_chip(cfg):
    import jax
    import numpy as np
    from repro.kernels.ops import KernelFallbackWarning

    log(f"model {cfg.name}: {cfg.n_layers} layers, d_model {cfg.d_model}, "
        f"heads {cfg.n_heads}/{cfg.n_kv_heads}, d_ff {cfg.d_ff}, "
        f"vocab {cfg.vocab}, weights {cfg.scheme_proj}/{cfg.scheme_ffn}")
    params, build_s = build_params(cfg)
    log(f"weight build: {build_s!r} s")
    prompts = requests_for(cfg.vocab)
    log(f"requests: {len(prompts)} greedy, prompt lengths "
        f"{[len(p) for p in prompts]}, {MAX_NEW} new tokens each")

    results = {}
    for kernel in ("pallas", "jnp"):
        engine = make_engine(cfg, params, kernel)
        with warnings.catch_warnings():
            if kernel == "pallas":   # every site must run its kernel
                warnings.simplefilter("error", KernelFallbackWarning)
            for tier in ("bf16", "int8"):
                t0 = time.perf_counter()
                toks = serve(engine, prompts, tier)
                warm_s = time.perf_counter() - t0
                t0 = time.perf_counter()
                again = serve(engine, prompts, tier)
                serve_s = time.perf_counter() - t0
                assert again == toks, f"{kernel}/{tier}: reruns differ"
                logits = first_step_logits(engine, prompts, tier)
                has_kernel = decode_step_has_kernel(engine, tier)
                n_tok = sum(map(len, toks))
                log(f"[{kernel} kernel, {tier} KV] compile+first serve "
                    f"(warm-up) {warm_s!r} s; served {n_tok} tokens in "
                    f"{serve_s!r} s; decode step tpu_custom_call: "
                    f"{has_kernel}")
                if kernel == "pallas":
                    assert has_kernel, "pallas decode step holds no kernel"
                results[kernel, tier] = (toks, logits)
        del engine
    for tier in ("bf16", "int8"):
        (pt, (pp, pd)), (jt, (jp, jd)) = (results["pallas", tier],
                                          results["jnp", tier])
        log(f"pallas vs jnp, {tier} KV:")
        compare("prefill last-position logits", pp, jp)
        compare("first decode-step logits", pd, jd)
        first = float(np.mean(pp.argmax(-1) == jp.argmax(-1)))
        log(f"  first greedy token agrees on {first!r} of requests; "
            f"greedy tokens agree position by position on "
            f"{agreement(pt, jt)!r}")
    log(f"peak device memory: {peak_bytes(jax.devices()[0])!r} bytes")


def run_four_chips(cfg):
    import jax
    from repro.kernels.ops import KernelFallbackWarning, reset_site_warnings
    from repro.launch.cli import serving_mesh
    from repro.runtime.partitioning import spec_maker

    mesh = serving_mesh(1, 4)
    log(f"mesh: data=1 x model=4 over {[d.id for d in mesh.devices.flat]}")

    def sharded_run(cfg, label):
        params, build_s = build_params(
            cfg, specs=spec_maker(cfg, mesh, train=False))
        log(f"[{label}] weight build on the mesh: {build_s!r} s")
        engine = make_engine(cfg, params, "pallas", mesh=mesh)
        reset_site_warnings()   # each site warns once per process
        with warnings.catch_warnings(record=True) as caught:
            warnings.simplefilter("always", KernelFallbackWarning)
            t0 = time.perf_counter()
            toks = serve(engine, prompts, "bf16")
            warm_s = time.perf_counter() - t0
            logits = first_step_logits(engine, prompts, "bf16")
        sites = sorted({w.message.site for w in caught
                        if isinstance(w.message, KernelFallbackWarning)})
        log(f"[{label}] compile+serve {warm_s!r} s, "
            f"{sum(map(len, toks))} tokens; jnp fallback sites: {sites}")
        stray = [s for s in sites if not s.startswith("moe.")]
        assert not stray, f"kernel sites fell back off the expert path: {stray}"
        return toks, logits

    prompts = requests_for(cfg.vocab)
    log(f"model {cfg.name}: {cfg.n_layers} layers, d_model {cfg.d_model}, "
        f"{cfg.n_experts} experts top-{cfg.top_k}, vocab {cfg.vocab}; "
        f"{len(prompts)} greedy requests, prompt lengths "
        f"{[len(p) for p in prompts]}, {MAX_NEW} new tokens each")
    sharded_run(cfg, "full depth")
    for d in mesh.devices.flat:
        log(f"  device {d.id} peak memory: {peak_bytes(d)!r} bytes")

    cut = dataclasses.replace(cfg, n_layers=CUT_LAYERS)
    log(f"cut to {CUT_LAYERS} of {cfg.n_layers} layers: meshless on device "
        f"0 vs the 1x4 mesh")
    mesh_toks, (mp, md) = sharded_run(cut, "cut depth, mesh")
    params, build_s = build_params(cut)
    engine = make_engine(cut, params, "pallas")
    with warnings.catch_warnings():
        warnings.simplefilter("error", KernelFallbackWarning)
        one_toks = serve(engine, prompts, "bf16")
        sp, sd = first_step_logits(engine, prompts, "bf16")
    log(f"[cut depth, device 0] weight build {build_s!r} s, "
        f"{sum(map(len, one_toks))} tokens")
    log("mesh vs meshless:")
    compare("prefill last-position logits", mp, sp)
    compare("first decode-step logits", md, sd)
    log(f"  greedy tokens agree position by position on "
        f"{agreement(mesh_toks, one_toks)!r}")
    for d in mesh.devices.flat:
        log(f"  device {d.id} peak memory: {peak_bytes(d)!r} bytes")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: granite-8b on one chip; 4: qwen3-moe-30b-a3b "
                         "on a 1x4 mesh and its cut-depth comparison")
    args = ap.parse_args(argv)
    try:
        import jax
        from repro.configs import get_config
        from repro.launch.cli import enable_compile_cache
    except ImportError as e:
        print(f"chip_smoke: cannot import the program: {e}", file=sys.stderr)
        return 2
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {devices[0].platform}); "
              "this script measures nothing elsewhere", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} TPUs, "
              f"JAX found {len(devices)}", file=sys.stderr)
        return 1
    log(f"compile cache: {enable_compile_cache()}")
    log(f"device_kind: {devices[0].device_kind}; {len(devices)} devices")
    if args.chips == 4:
        run_four_chips(get_config("qwen3-moe-30b-a3b"))
    else:
        run_one_chip(get_config("granite-8b"))
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
