"""Continuous-batching serving benchmark: Poisson arrivals over the slot
pool, reporting the serving-level metrics the paper's end-to-end workloads
are judged by (TTFT, inter-token latency, tokens/s, slot occupancy).

Requests arrive by a seeded Poisson process while the scheduler steps, so
later requests are admitted mid-flight — between decode steps of the
earlier ones — exercising chunked-prefill interleaving and slot reuse
exactly as production traffic would.

``--kv-dtype`` sweeps the pool storage dtype (DESIGN.md §9): each sweep
point runs the same seeded workload at one dtype and reports slots x tok/s
x TTFT for its cache cost.  With ``--cache-budget-mb`` the slot count is
*derived* from the budget per dtype, so the sweep directly measures the
quantization -> concurrency trade (int8/fp8 fit ~2x the slots of bf16).
One JSON is emitted per sweep point (``--out-dir`` to write files);
``--baseline-json PATH`` appends the whole sweep (bench args + points) to
PATH, so multi-regime baselines are built by invoking the bench several
times against the same file.  ``benchmarks/BENCH_serve_baseline.json`` is
produced exactly that way:

    rm -f benchmarks/BENCH_serve_baseline.json
    python benchmarks/serve_bench.py --kv-dtype bf16,int8 --requests 6 \
        --rate 1 --seed 6 --max-new 33 --max-burst 8 \
        --baseline-json benchmarks/BENCH_serve_baseline.json
    # ... then the same line with --max-burst 1, the contended pair
    # (--requests 8 --rate 3 --seed 0) at --max-burst 8 and 1, and the
    # mixed-tier capacity sweep (DESIGN.md §12):
    python benchmarks/serve_bench.py --tiers bf16,int8 --d-head 128 \
        --cache-budget-mb 1 --requests 8 --rate 2 --seed 0 --max-new 16 \
        --max-burst 8 --baseline-json benchmarks/BENCH_serve_baseline.json
    # ... and the weight-kernel pair (DESIGN.md §14) — the first regime at
    # --weight-kernel on (packed Pallas kernels on the decode weight path)
    # and --weight-kernel off (jnp dequantize-then-dot), so the baseline
    # records the serving metrics of BOTH weight paths
    # ... and the paged-vs-slab pair (DESIGN.md §15): the shared-prefix
    # workload at a fixed cache budget, once on the slab pool and once
    # with --paged:
    python benchmarks/serve_bench.py --kv-dtype bf16 --requests 12 \
        --rate 20 --seed 2 --prefix-len 32 --prefix-share 0.75 \
        --prompt-len 16 --max-new 16 --n-slots 12 --cache-budget-mb 2 \
        --max-burst 8 --baseline-json benchmarks/BENCH_serve_baseline.json
    # ... then the same line with --paged.  The paged point reports
    # prefix hit-rate, hit-vs-miss TTFT, pages in use, and a
    # peak_in_flight_requests that the slab point cannot reach at the
    # same budget (worst-case slot reservation vs pages actually used).

Shared-prefix workload knobs (``--prefix-len N --prefix-share F``): a
fraction F of requests carry ONE common N-token prefix ahead of their
unique tail; every point (slab or paged) reports
``peak_in_flight_requests``, and paged points add prefix hit/miss
counts, hit-vs-miss TTFT split (from ServeMetrics), page-size/arena
geometry and peak/cached page counts.  Every point also carries an
``env`` stamp (jax/jaxlib versions, backend, device kind) so committed
baselines stay attributable across environments.

``--max-burst`` caps the device-resident decode burst (DESIGN.md §11);
each point reports ``decode_dispatches_per_token``, ``host_syncs_per_token``
and a burst-length histogram, so sweeping ``--max-burst 1`` vs ``8``
measures the dispatch/sync amortization directly — pool geometry is a pure
function of the workload shape, identical across burst caps.  Warmup
compiles the whole power-of-two burst ladder off the clock (one throwaway
request per reachable burst length), so the timed run is steady-state.

``--tiers bf16,int8`` switches to MIXED-TIER mode (DESIGN.md §12): ONE
engine serves every named KV tier concurrently — one pool per tier
(budget-derived slots per tier with ``--cache-budget-mb``), requests
assigned tiers round-robin via ``Request.kv_policy``, decode batches
cohorted per tier by the scheduler.  The point reports per-tier slot
counts and ``tier_slot_ratio_vs_bf16`` — at ``--d-head 128`` (the paper
models' head dim; smoke configs default to 16) the int8 tier fits ~1.94x
the bf16 slots from the same budget, served from the same engine.
``--policy policy.json`` drives the engine from a serialized
``PrecisionPolicy`` instead of the legacy flags (which keep working and
print their policy equivalent).

``--trace-dir`` writes per-point Chrome traces (Perfetto-loadable),
Prometheus-style expositions and registry snapshots (DESIGN.md §13);
``--hlo-cost`` joins trip-count-aware FLOP/byte counts of the compiled
step.

**Adversarial workloads + SLO (DESIGN.md §16)**: ``--arrival bursty
--burst-size B`` replaces the smooth Poisson process with Poisson-spaced
bursts of B simultaneous arrivals, and ``--prompt-dist heavy`` draws
prompt lengths from a clipped Pareto (many short, a heavy tail at
``--prompt-len``) — the two shapes that break schedulers tuned on smooth
traffic.  ``--priority-mix 0:0.25,5:0.75`` assigns seeded priority
classes; the report then carries per-priority TTFT/e2e percentiles and
queue waits.  ``--slo-max-waiting`` / ``--slo-max-queue-delay-s`` /
``--slo-downgrade FROM:TO --slo-high-s H --slo-low-s L`` /
``--slo-max-step-s`` attach a ``serve.slo.SLOPolicy`` (admission
control, tier downgrade with hysteresis, cost-model burst planning).
``--fault-rate R`` arms a seeded fault injector AFTER warmup: each
engine dispatch dies (or NaN-poisons) with probability R and the
scheduler recovers by preempt-and-requeue — the bench asserts the
accounting identity (every submitted request lands in exactly one
finish reason) on every run, faults or not.  The committed overload
pair in ``BENCH_serve_baseline.json``:

    # ~2x sustained overload, FCFS: every class queues behind everyone,
    # so tail TTFT ~ the whole backlog drain (grows without bound as
    # load is sustained)
    python benchmarks/serve_bench.py --requests 24 --rate 40 --seed 0 \
        --n-slots 2 --max-new 16 --max-burst 8 --arrival bursty \
        --burst-size 4 --prompt-dist heavy \
        --baseline-json benchmarks/BENCH_serve_baseline.json
    # same workload, 25% priority-0 traffic + admission control: the
    # high class preempts its way to a bounded p99 TTFT (~20x below the
    # FCFS tail) while best-effort is queued/shed with typed rejections
    # (counters account for every submitted request)
    python benchmarks/serve_bench.py --requests 24 --rate 40 --seed 0 \
        --n-slots 2 --max-new 16 --max-burst 8 --arrival bursty \
        --burst-size 4 --prompt-dist heavy --priority-mix 0:0.25,5:0.75 \
        --slo-max-waiting 8 \
        --baseline-json benchmarks/BENCH_serve_baseline.json

**Speculative decoding (DESIGN.md §17)**: ``--spec`` attaches the
draft/verify engine — K draft tokens under ``--draft-policy``'s
aggressive KV tier (default int8, the self-drafting configuration:
SAME weights, cheaper numerics), verified in ONE target dispatch with
longest-agreeing-prefix acceptance.  Accepted output is bit-identical
to a spec-off run; the point reports the ``spec`` metrics block
(acceptance rate, accepted-per-verify-dispatch, spec-aware
dispatches_per_token) plus the planner snapshot.  ``--spec-corrupt``
garbles every draft (seeded collapse harness), demonstrating the
K-controller's fall-back to plain bursts.  The committed spec triple:

    # the dispatch-reduction pair: per-token target dispatch cadence
    # (--max-burst 1), spec off vs on — spec-on emits >1 accepted token
    # per verify dispatch, cutting dispatches-per-token below the
    # one-per-token floor
    python benchmarks/serve_bench.py --kv-dtype bf16 --requests 6 \
        --rate 2 --seed 9 --max-new 33 --max-burst 1 \
        --baseline-json benchmarks/BENCH_serve_baseline.json
    python benchmarks/serve_bench.py --kv-dtype bf16 --requests 6 \
        --rate 2 --seed 9 --max-new 33 --max-burst 1 --spec \
        --baseline-json benchmarks/BENCH_serve_baseline.json
    # the collapse guard: corrupted drafts (0 acceptance) against the
    # plain-burst reference (same line without --spec/--spec-corrupt) —
    # the planner collapses to plain bursts and switches off after
    # max_collapses failed probes, so dispatches_per_token stays within
    # probe-overhead of spec-off
    python benchmarks/serve_bench.py --kv-dtype bf16 --requests 6 \
        --rate 2 --seed 9 --max-new 33 --max-burst 8 \
        --baseline-json benchmarks/BENCH_serve_baseline.json
    python benchmarks/serve_bench.py --kv-dtype bf16 --requests 6 \
        --rate 2 --seed 9 --max-new 33 --max-burst 8 --spec \
        --spec-corrupt \
        --baseline-json benchmarks/BENCH_serve_baseline.json

Smoke (CPU, ~1 min incl. compile):
    python benchmarks/serve_bench.py
Burst amortization sweep:
    python benchmarks/serve_bench.py --max-burst 1 --out-dir bench_out
    python benchmarks/serve_bench.py --max-burst 8 --out-dir bench_out
Quantized-cache sweep at a fixed budget:
    python benchmarks/serve_bench.py --kv-dtype bf16,fp8,int8 \
        --cache-budget-mb 2 --out-dir bench_out
Sharded sweep on forced host devices (DESIGN.md §10):
    python benchmarks/serve_bench.py --dp 2 --tp 4 --force-host-devices 8 \
        --kv-dtype int8 --out-dir bench_out
Mixed-tier serving from one engine (DESIGN.md §12):
    python benchmarks/serve_bench.py --tiers bf16,int8 --d-head 128 \
        --cache-budget-mb 1 --out-dir bench_out
"""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

# NOTE: jax (and repro modules, which import it) are imported inside main()
# so --force-host-devices can set XLA_FLAGS before backend initialization
# (repro.launch.cli is deliberately jax-free at module level).
import numpy as np

from repro.launch.cli import (enable_compile_cache, force_host_devices,
                              serving_mesh)


def parse_priority_mix(spec):
    """``"0:0.25,5:0.75"`` -> (classes, normalized weights) or None."""
    if not spec:
        return None
    classes, weights = [], []
    for part in spec.split(","):
        prio, w = part.split(":")
        classes.append(int(prio))
        weights.append(float(w))
    total = sum(weights)
    if total <= 0:
        raise SystemExit("--priority-mix weights must sum > 0")
    return classes, [w / total for w in weights]


def build_slo(args):
    """An ``SLOPolicy`` from the --slo-* flags, or None when none given."""
    downgrade = None
    if args.slo_downgrade:
        src, dst = args.slo_downgrade.split(":")
        downgrade = {src: dst}
    if not any([args.slo_max_waiting, args.slo_max_queue_delay_s,
                downgrade, args.slo_max_step_s]):
        return None
    from repro.serve import SLOPolicy
    return SLOPolicy(
        max_waiting=args.slo_max_waiting,
        max_queue_delay_s=args.slo_max_queue_delay_s,
        protect_priority=args.slo_protect_priority,
        downgrade_map=downgrade,
        downgrade_high_s=args.slo_high_s,
        downgrade_low_s=args.slo_low_s,
        max_step_s=args.slo_max_step_s)


def build_fault_injector(args):
    """Seeded ``(kind, seq) -> mode`` injector, DISARMED until the timed
    run starts (warmup compiles off the clock and must not fault).
    Deterministic: its own generator, decoupled from the workload rng, is
    consulted once per dispatch in dispatch order.  Returns
    (injector, arm) — call ``arm()`` after warmup."""
    if not args.fault_rate:
        return None, lambda: None
    frng = np.random.default_rng(args.seed + 7919)
    armed = []

    def injector(kind, seq):
        if not armed or frng.random() >= args.fault_rate:
            return None
        # half the faults kill the dispatch (StepFault), half NaN-poison
        # the sampled tokens (prefill kills regardless: 'nan' only
        # applies to decode paths, see ServeConfig.fault_injector)
        return "nan" if frng.random() < 0.5 else "injected"

    return injector, lambda: armed.append(True)


def build_spec(args):
    """A ``SpecConfig`` from the --spec-* flags, or None when --spec is
    off.  ``--draft-policy`` names the draft KV tier (int8/fp8/bf16) or a
    PrecisionPolicy JSON path for the whole draft engine."""
    if not args.spec:
        return None
    from repro.serve import SpecConfig
    draft_kv, draft_policy = args.draft_policy, None
    if os.path.exists(args.draft_policy):
        from repro.quant.policy import PrecisionPolicy
        with open(args.draft_policy) as f:
            draft_policy = PrecisionPolicy.from_json(f.read())
        draft_kv = draft_policy.kv
    return SpecConfig(draft_kv=draft_kv, draft_policy=draft_policy,
                      k_max=args.spec_k, k_init=args.spec_k,
                      corrupt_drafts=args.spec_corrupt)


def build_engine(args, cfg, params, kv_dtype, mesh, policy=None,
                 fault_injector=None):
    import dataclasses

    from repro.quant.policy import PrecisionPolicy
    from repro.serve import ServeConfig, ServingEngine
    budget = int(args.cache_budget_mb * 1e6) if args.cache_budget_mb else None
    if policy is None:
        policy = PrecisionPolicy.from_legacy(kv_dtype=kv_dtype)
    elif policy.kv != kv_dtype:
        # --policy + a --kv-dtype sweep: each point re-tiers the policy
        policy = dataclasses.replace(policy, kv=kv_dtype)
    if args.weight_kernel != "auto":
        # --weight-kernel on|off pins the decode-step weight path: 'on'
        # routes quantized linears through the packed Pallas kernels
        # (packed_gemv/w8a8_matmul, DESIGN.md §14), 'off' pins the jnp
        # dequantize-then-dot fallback.  'auto' keeps the policy default
        # (pallas under a multi-device mesh, jnp meshless).
        policy = dataclasses.replace(
            policy, kernel={"on": "pallas", "off": "jnp"}[args.weight_kernel])
    # NOTE: pool geometry (max_len, and any budget-derived slot count) is a
    # pure function of the workload shape — NOT of --max-burst — so sweep
    # points at different burst caps measure dispatch amortization against
    # an identical engine configuration
    scfg = ServeConfig(max_len=args.prefix_len + args.prompt_len
                       + args.max_new,
                       temperature=args.temperature,
                       n_slots=args.n_slots, prefill_chunk=args.chunk,
                       cache_budget_bytes=budget,
                       paged=args.paged, page_size=args.page_size,
                       max_burst=args.max_burst, mesh=mesh, policy=policy,
                       fault_injector=fault_injector,
                       max_fault_retries=args.max_fault_retries)
    engine = ServingEngine(cfg, params, scfg)
    print(f"== precision policy: {engine.policy.to_json()}")
    return engine


def make_workload(args, vocab):
    """Seeded arrivals with jittered prompt lengths and priority classes.

    Arrivals: ``--arrival poisson`` (default) is the smooth process;
    ``--arrival bursty --burst-size B`` draws Poisson-spaced burst epochs
    at rate/B and drops B simultaneous arrivals on each — same long-run
    rate, adversarial short-run backlog (DESIGN.md §16).

    Prompt lengths: uniform jitter by default; ``--prompt-dist heavy``
    draws a clipped Pareto (alpha=1.2) — mostly short prompts with a
    heavy tail pinned at ``--prompt-len``, so occasional giants stall
    chunked prefill behind them.  Both stay within the slot geometry
    (``max_len`` is sized from ``--prompt-len``).

    Priorities: ``--priority-mix "0:0.25,5:0.75"`` assigns each request a
    seeded class draw (smaller = more important); None -> all class 0.

    With ``--prefix-len N --prefix-share F`` a fraction F of the requests
    share ONE common N-token prefix ahead of their unique tail (the
    shared-system-prompt workload); the rest get fully unique prompts of
    the same total length, so the two cohorts differ only in
    shareability.  On a paged pool the shared cohort prefix-hits once the
    first of them has prefilled and registered (DESIGN.md §15); on the
    slab pool the same workload measures the no-sharing baseline."""
    rng = np.random.default_rng(args.seed)
    if args.arrival == "bursty" and args.burst_size > 1:
        B = args.burst_size
        n_bursts = -(-args.requests // B)
        epochs = np.cumsum(rng.exponential(B / args.rate, n_bursts))
        arrivals = np.repeat(epochs, B)[:args.requests]
    else:
        arrivals = np.cumsum(rng.exponential(1.0 / args.rate, args.requests))
    arrivals = arrivals - arrivals[0]      # first request starts the clock
    if args.prompt_dist == "heavy":
        scale = max(2, args.prompt_len // 8)
        raw = (rng.pareto(1.2, args.requests) + 1.0) * scale
        lens = np.clip(raw.astype(np.int64), 2, args.prompt_len)
    else:
        lens = rng.integers(max(2, args.prompt_len // 2),
                            args.prompt_len + 1, args.requests)
    shared = rng.random(args.requests) < args.prefix_share
    prefix = rng.integers(1, vocab, (args.prefix_len,)).astype(np.int32)
    prompts = []
    for n, s in zip(lens, shared):
        tail = rng.integers(1, vocab,
                            (int(n) + (0 if s else args.prefix_len),)
                            ).astype(np.int32)
        prompts.append(np.concatenate([prefix, tail]) if s else tail)
    mix = parse_priority_mix(args.priority_mix)
    if mix is None:
        priorities = np.zeros(args.requests, np.int64)
    else:
        classes, weights = mix
        priorities = rng.choice(classes, size=args.requests, p=weights)
    return arrivals, prompts, priorities


def warmup(engine, prompts, max_new, tiers=None, spec=None):
    """Compile the chunk/decode/burst steps off the clock so the first
    request's TTFT measures scheduling, not XLA.

    The timed run can only ever plan power-of-two burst lengths
    K <= min(max_burst, max_new - 1) (a row's remaining budget after its
    prefill-sampled first token is max_new - 1), so one throwaway request
    per such K — with max_new = K + 1, whose lone burst is planned exactly
    K — compiles the complete ladder without touching the engine's pool
    geometry.  With ``tiers`` the ladder runs once per KV tier (each tier
    is its own compiled step set, keyed per pool in the engine).

    With ``spec`` the draft/verify ladder compiles too: one throwaway
    request per reachable K (the planner can halve down to 1, so the
    whole power-of-two ladder <= k_max), each through a scheduler pinned
    at k_init = k_max = K.  The DraftEngine caches its inner compute
    engine on the target engine, so the timed scheduler's own DraftEngine
    reuses every draft/verify compile from here."""
    import dataclasses

    from repro.serve import Request, SamplingParams, Scheduler
    sched = Scheduler(engine, tiers=tiers)
    top = min(engine.scfg.max_burst, max(max_new - 1, 1))
    ladder = [1 << i for i in range(top.bit_length()) if (1 << i) <= top]
    for k in ladder:
        for tier in (tiers or [None]):
            sched.submit(Request(prompt=prompts[0], kv_policy=tier,
                                 sampling=SamplingParams(
                                     temperature=engine.scfg.temperature,
                                     max_new_tokens=k + 1)))
            sched.run(max_steps=200)
    if spec is None:
        return
    stop = min(spec.k_max, max(max_new - 2, 1))
    for k in [1 << i for i in range(stop.bit_length()) if (1 << i) <= stop]:
        # k_init == k_max == K pins the first spec round's draft length at
        # exactly K (budget max_new-1 = K+1 covers the K+1-token window),
        # compiling the K-step draft burst and the S=K+1 verify
        wcfg = dataclasses.replace(spec, k_init=k, k_max=k,
                                   corrupt_drafts=False)
        wsched = Scheduler(engine, tiers=tiers, spec=wcfg)
        for tier in (tiers or [None]):
            wsched.submit(Request(prompt=prompts[0], kv_policy=tier,
                                  sampling=SamplingParams(
                                      temperature=engine.scfg.temperature,
                                      max_new_tokens=k + 2)))
            wsched.run(max_steps=200)


def point_label(cfg, kv_dtype, tiers, max_burst, weight_kernel="auto",
                paged=False, args=None):
    label = "+".join(tiers) if tiers else kv_dtype
    stem = f"serve_{cfg.name}_{label.replace('+', '-')}_burst{max_burst}"
    if weight_kernel != "auto":
        stem += f"_wk{weight_kernel}"   # --weight-kernel on|off points
    if paged:
        stem += "_paged"                # paged-vs-slab pairs (DESIGN.md §15)
    if args is not None:                # adversarial pairs (DESIGN.md §16):
        if args.priority_mix:           # FCFS-vs-priority points must not
            stem += "_prio"             # collide in a shared --out-dir
        if args.fault_rate:
            stem += "_fault"
        if args.spec:                   # spec-on/off pairs (DESIGN.md §17)
            stem += "_speccorrupt" if args.spec_corrupt else "_spec"
    return stem


def bench_env():
    """Environment stamp carried by every bench point: the perf
    trajectory in a committed baseline is only attributable if each point
    records what software/hardware produced it."""
    import jax
    import jaxlib
    dev = jax.devices()[0]
    return {"jax": jax.__version__, "jaxlib": jaxlib.__version__,
            "backend": jax.default_backend(),
            "device_kind": dev.device_kind, "n_devices": jax.device_count()}


def run_point(args, cfg, engine, kv_dtype, tiers=None, arm_fault=None):
    """One sweep point: the seeded workload at one pool dtype — or, with
    ``tiers``, the MIXED-TIER workload: one engine, one pool per KV tier,
    requests assigned tiers round-robin (``Request.kv_policy``) so
    bf16/int8/fp8 traffic interleaves, mid-flight admission included.

    With ``--trace-dir`` the point also writes a Chrome trace, a
    Prometheus-style exposition and periodic registry snapshots
    (DESIGN.md §13)."""
    from repro.obs import (MetricsRegistry, Observability, SnapshotWriter,
                           Tracer)
    from repro.serve import Request, SamplingParams, Scheduler
    arrivals, prompts, priorities = make_workload(args, cfg.vocab)
    spec = build_spec(args)
    if not args.no_warmup:
        t0 = time.monotonic()
        warmup(engine, prompts, args.max_new, tiers=tiers, spec=spec)
        print(f"== warmup (compile) {time.monotonic() - t0:.1f}s")
    if arm_fault is not None:
        arm_fault()        # faults only in the timed run, never in warmup
    slo = build_slo(args)
    if slo is not None:
        print(f"== slo: {json.dumps(slo.snapshot())}")

    obs = stem = None
    if args.trace_dir:
        os.makedirs(args.trace_dir, exist_ok=True)
        stem = os.path.join(args.trace_dir,
                            point_label(cfg, kv_dtype, tiers, args.max_burst,
                                        args.weight_kernel, args.paged,
                                        args=args))
        registry = MetricsRegistry()
        obs = Observability(tracer=Tracer(), registry=registry,
                            snapshots=SnapshotWriter(
                                registry, stem + ".metrics.jsonl"))
    sched = Scheduler(engine, tiers=tiers, obs=obs, slo=slo, spec=spec)
    for tier, pool in sorted(sched.pools.items()):
        print(f"== pool[{tier}]: {pool.n_slots} slots x {pool.max_len} "
              f"positions; {pool.bytes_per_token} B/token, "
              f"{pool.cache_bytes / 1e6:.2f} MB cache; prefill chunk "
              f"{args.chunk}; {args.requests} requests @ ~{args.rate}/s")
    reqs = []
    admitted_after_first_decode = 0
    peak_in_flight = 0          # concurrent admitted (PREFILL+DECODE) reqs
    peak_pages = 0              # paged pools: peak arena pages in use
    i = 0
    t0 = time.monotonic()
    while i < args.requests or sched.has_work:
        now = time.monotonic() - t0
        while i < args.requests and arrivals[i] <= now:
            if sched.n_decode_steps > 0:
                admitted_after_first_decode += 1
            reqs.append(sched.submit(Request(
                prompt=prompts[i],
                kv_policy=tiers[i % len(tiers)] if tiers else None,
                priority=int(priorities[i]),
                sampling=SamplingParams(temperature=args.temperature,
                                        max_new_tokens=args.max_new,
                                        seed=args.seed))))
            i += 1
        if sched.has_work:
            sched.step()
            peak_in_flight = max(peak_in_flight, sum(
                1 for r in reqs if r.slot is not None and not r.is_finished))
            peak_pages = max(peak_pages, sum(
                p.pages_in_use for p in sched.pools.values()
                if getattr(p, "paged", False)))
        elif i < args.requests:
            time.sleep(min(float(arrivals[i]) - now, 0.01))

    assert all(r.is_finished for r in reqs)
    # accounting identity (DESIGN.md §16): every submitted request —
    # including rejected/shed/faulted ones, which never emit a token —
    # lands in exactly one finish reason
    finish_reasons = dict(sched.metrics.finish_reasons)
    assert sum(finish_reasons.values()) == len(reqs) == args.requests, \
        (finish_reasons, len(reqs))
    # token accounting identity (DESIGN.md §17): every emitted token is a
    # prefill first token, a plain decode emission, or a spec-round
    # emission — speculation must never double-count or drop tokens
    m = sched.metrics
    assert m.total_new_tokens == (len(m.ttft) + m.decode_tokens_emitted
                                  + m.spec_tokens_emitted), \
        (m.total_new_tokens, len(m.ttft), m.decode_tokens_emitted,
         m.spec_tokens_emitted)
    print(f"\n{'req':>4} {'arrive':>7} {'tier':>5} {'prio':>4} {'P':>4} "
          f"{'new':>4} {'ttft_s':>7} {'e2e_s':>7}  reason")
    for a, r in zip(arrivals, reqs):
        # rejected / deadline-shed / faulted requests may never have
        # emitted a first token
        ttft = (f"{r.first_token_time - r.arrival_time:>7.3f}"
                if r.first_token_time is not None else f"{'-':>7}")
        e2e = (f"{r.finish_time - r.arrival_time:>7.3f}"
               if r.finish_time is not None else f"{'-':>7}")
        print(f"{r.id:>4} {a:>7.2f} {r.tier:>5} {r.priority:>4} "
              f"{r.prompt_len:>4} {r.n_generated:>4} {ttft} {e2e}  "
              f"{r.finish_reason}")

    pool = sched.pool
    rep = sched.metrics.report()
    rep["scheduler_steps"] = sched.n_steps
    rep["decode_steps"] = sched.n_decode_steps
    rep["admitted_mid_flight"] = admitted_after_first_decode
    rep["kv_dtype"] = "+".join(tiers) if tiers else kv_dtype
    rep["n_slots"] = sum(p.n_slots for p in sched.pools.values())
    rep["env"] = bench_env()
    # in-flight concurrency is THE paged-vs-slab capacity number: at a
    # fixed cache budget the slab admits worst-case-sized slots, the
    # paged pool admits on pages actually needed (+ prefix sharing)
    rep["peak_in_flight_requests"] = peak_in_flight
    rep["paged"] = bool(args.paged)
    # SLO / adversarial-workload stamp (DESIGN.md §16): workload shape +
    # policy state, so committed overload points are self-describing
    rep["n_submitted"] = len(reqs)
    rep["arrival"] = args.arrival
    if args.arrival == "bursty":
        rep["burst_size"] = args.burst_size
    rep["prompt_dist"] = args.prompt_dist
    if args.priority_mix:
        rep["priority_mix"] = args.priority_mix
    if args.fault_rate:
        rep["fault_rate"] = args.fault_rate
    if slo is not None:
        rep["slo"] = slo.snapshot()
    if args.paged:
        rep["page_size"] = pool.page_size
        rep["n_pages"] = sum(p.n_pages for p in sched.pools.values())
        rep["pages_in_use_peak"] = peak_pages
        rep["pages_cached_final"] = sum(p.pages_cached
                                        for p in sched.pools.values())
        rep["prefix_hits"] = sum(p.n_prefix_hits
                                 for p in sched.pools.values())
        rep["prefix_misses"] = sum(p.n_prefix_misses
                                   for p in sched.pools.values())
        rep["prefix_hit_tokens"] = sum(p.prefix_hit_tokens_total
                                       for p in sched.pools.values())
    if args.prefix_len:
        rep["prefix_len"] = args.prefix_len
        rep["prefix_share"] = args.prefix_share
    if not tiers:
        # scalar bytes/token is only meaningful for a single-tier pool;
        # mixed points carry tier_bytes_per_token instead
        rep["kv_bytes_per_token"] = pool.bytes_per_token
    rep["kv_cache_mb"] = round(
        sum(p.cache_bytes for p in sched.pools.values()) / 1e6, 3)
    if tiers:
        # the mixed-tier capacity story (DESIGN.md §12): per-tier slot
        # counts from ONE engine's budget — the int8/fp8 tiers fit ~1.9-2x
        # the bf16 slots at d_head=128, served concurrently
        rep["tier_slots"] = {t: p.n_slots
                             for t, p in sorted(sched.pools.items())}
        rep["tier_bytes_per_token"] = {
            t: p.bytes_per_token for t, p in sorted(sched.pools.items())}
        rep["tier_new_tokens"] = {
            t: sum(r.n_generated for r in reqs if r.tier == t)
            for t in sorted(sched.pools)}
        if "bf16" in sched.pools:
            base = sched.pools["bf16"].n_slots
            rep["tier_slot_ratio_vs_bf16"] = {
                t: round(p.n_slots / base, 4)
                for t, p in sorted(sched.pools.items())}
    # burst amortization (DESIGN.md §11): dispatches / host syncs per token
    # (decode_dispatches_per_token and burst_hist come from the metrics
    # report itself)
    rep["max_burst"] = sched.max_burst
    rep["weight_kernel"] = engine.policy.kernel
    rep["host_syncs"] = sched.n_host_syncs
    if rep.get("total_new_tokens"):
        rep["host_syncs_per_token"] = round(
            sched.n_host_syncs / rep["total_new_tokens"], 4)
    if spec is not None:
        # speculative point stamp (DESIGN.md §17): config + controller
        # end-state, plus the analytical draft/verify price at the
        # MEASURED acceptance — the model-vs-measured join for spec mode
        rep["spec_args"] = {"draft_kv": spec.draft_kv, "k_max": spec.k_max,
                            "corrupt_drafts": spec.corrupt_drafts}
        rep["spec_planner"] = sched.spec_planner.snapshot()
        from repro.perfmodel.analytical import spec_round_latency
        acc = (rep.get("spec") or {}).get("acceptance_rate") or 0.0
        dpool = (sched.draft.pools.get(sched.default_tier)
                 if sched.draft is not None else None)
        rep["spec_model"] = spec_round_latency(
            cfg, k=spec.k_max, batch=rep["n_slots"],
            context=engine.scfg.max_len, acceptance=acc,
            kv_bytes_per_token=(None if tiers else pool.bytes_per_token),
            draft_kv_bytes_per_token=(dpool.bytes_per_token
                                      if dpool is not None else None))
    if args.cache_budget_mb:
        rep["cache_budget_mb"] = args.cache_budget_mb
    if args.hlo_cost:
        # static compiled-step costs per pool (trip-count-aware HLO walk);
        # offline lowering — never touches the timed run above
        from repro.launch.hlo_analysis import compiled_step_cost
        rep["compiled_step_cost"] = {
            t: compiled_step_cost(engine, p)
            for t, p in sorted(sched.pools.items())}
    if stem is not None:
        obs.tracer.write(stem + ".trace.json")
        with open(stem + ".metrics.txt", "w") as f:
            f.write(obs.registry.expose())
        print(f"== trace: {stem}.trace.json ({len(obs.tracer)} events); "
              f"metrics: {stem}.metrics.txt "
              f"(+{obs.snapshots.n_written} snapshots)")
    return rep


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="granite-8b")
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--requests", type=int, default=10)
    ap.add_argument("--rate", type=float, default=6.0, help="req/s (Poisson)")
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--n-slots", type=int, default=8)
    ap.add_argument("--chunk", type=int, default=8)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--no-warmup", action="store_true")
    ap.add_argument("--arrival", default="poisson",
                    choices=["poisson", "bursty"],
                    help="arrival process: smooth Poisson, or Poisson-"
                         "spaced bursts of --burst-size simultaneous "
                         "arrivals at the same long-run rate "
                         "(DESIGN.md §16)")
    ap.add_argument("--burst-size", type=int, default=4,
                    help="arrivals per burst in --arrival bursty mode")
    ap.add_argument("--prompt-dist", default="uniform",
                    choices=["uniform", "heavy"],
                    help="prompt-length law: uniform jitter, or 'heavy' "
                         "(clipped Pareto: mostly short, heavy tail at "
                         "--prompt-len)")
    ap.add_argument("--priority-mix", default=None,
                    help="seeded priority classes, e.g. '0:0.25,5:0.75' "
                         "(class:weight; smaller = more important). "
                         "Default: every request class 0 (pure FCFS)")
    ap.add_argument("--fault-rate", type=float, default=0.0,
                    help="per-dispatch fault probability in the timed run "
                         "(seeded; half killed dispatches, half NaN-"
                         "poisoned tokens).  The scheduler recovers by "
                         "preempt-and-requeue with bounded retries")
    ap.add_argument("--max-fault-retries", type=int, default=3,
                    help="step faults one request may survive before "
                         "finish_reason='fault'")
    ap.add_argument("--slo-max-waiting", type=int, default=None,
                    help="SLO: reject unprotected arrivals once this many "
                         "requests are queued")
    ap.add_argument("--slo-max-queue-delay-s", type=float, default=None,
                    help="SLO: reject unprotected arrivals once modeled "
                         "queue drain exceeds this")
    ap.add_argument("--slo-protect-priority", type=int, default=0,
                    help="SLO: requests with priority <= this are never "
                         "rejected")
    ap.add_argument("--slo-downgrade", default=None, metavar="FROM:TO",
                    help="SLO: kv-tier downgrade applied while degraded, "
                         "e.g. bf16:int8 (needs --slo-high-s/--slo-low-s "
                         "and --tiers naming both, so the target pool "
                         "exists)")
    ap.add_argument("--slo-high-s", type=float, default=None,
                    help="SLO: modeled drain that ENGAGES tier downgrade")
    ap.add_argument("--slo-low-s", type=float, default=None,
                    help="SLO: modeled drain that RELEASES it (< high)")
    ap.add_argument("--slo-max-step-s", type=float, default=None,
                    help="SLO: modeled per-round latency budget sizing "
                         "decode bursts / prefill chunks per step")
    ap.add_argument("--max-burst", type=int, default=8,
                    help="device-resident decode burst cap (1 = per-token "
                         "dispatch, DESIGN.md §11)")
    ap.add_argument("--spec", action="store_true",
                    help="speculative decoding (DESIGN.md §17): K draft "
                         "tokens under the aggressive --draft-policy tier, "
                         "verified in one target dispatch, longest-"
                         "agreeing-prefix acceptance — output stays "
                         "bit-identical to a spec-off run")
    ap.add_argument("--draft-policy", default="int8",
                    help="draft engine precision: a KV tier name "
                         "(int8/fp8/bf16) for the self-drafting "
                         "configuration, or a PrecisionPolicy JSON path")
    ap.add_argument("--spec-k", type=int, default=4,
                    help="draft-length ceiling k_max (power-of-two "
                         "ladder); the acceptance-EMA controller walks K "
                         "below it")
    ap.add_argument("--spec-corrupt", action="store_true",
                    help="adversarial collapse harness: garble every "
                         "draft token (0 acceptance) to demonstrate the "
                         "plain-burst fallback — output is STILL "
                         "bit-identical")
    ap.add_argument("--baseline-json", default=None,
                    help="write {args, points} for the whole sweep here")
    ap.add_argument("--kv-dtype", default="bf16",
                    help="comma-separated pool dtypes to sweep: bf16,fp8,int8")
    ap.add_argument("--tiers", default=None,
                    help="comma-separated KV tiers served CONCURRENTLY from "
                         "one engine (e.g. bf16,int8): requests are "
                         "assigned tiers round-robin via Request.kv_policy "
                         "(DESIGN.md §12).  One mixed point instead of a "
                         "per-dtype sweep")
    ap.add_argument("--paged", action="store_true",
                    help="serve from the paged KV pool (page-table arena, "
                         "COW prefix sharing, DESIGN.md §15) instead of "
                         "the fixed slab — run the same line with and "
                         "without this flag for a paged-vs-slab pair")
    ap.add_argument("--page-size", type=int, default=0,
                    help="positions per arena page (0 = prefill chunk)")
    ap.add_argument("--prefix-len", type=int, default=0,
                    help="shared-prefix workload: this many tokens of ONE "
                         "common prefix ahead of each shared request's "
                         "unique tail (0 disables)")
    ap.add_argument("--prefix-share", type=float, default=0.0,
                    help="fraction of requests that carry the shared "
                         "prefix (the rest get unique prompts of the same "
                         "total length)")
    ap.add_argument("--weight-kernel", default="auto",
                    choices=["auto", "on", "off"],
                    help="decode-step quantized weight path: 'on' pins the "
                         "packed Pallas kernels, 'off' pins the jnp "
                         "dequantize-then-dot fallback, 'auto' keeps the "
                         "policy default (pallas under a multi-device "
                         "mesh, jnp meshless) — DESIGN.md §14")
    ap.add_argument("--policy", default=None, metavar="POLICY_JSON",
                    help="path to a PrecisionPolicy JSON for the engine "
                         "(weight patterns + kv tier + kernel); legacy "
                         "flags keep working and print their policy "
                         "equivalent")
    ap.add_argument("--d-head", type=int, default=None,
                    help="override the config's head dim (e.g. 128 to run "
                         "the paper-scale KV geometry on a smoke-depth "
                         "model: the int8-vs-bf16 bytes/token ratio is "
                         "2*d/(d+4), so capacity claims need d_head=128)")
    ap.add_argument("--cache-budget-mb", type=float, default=None,
                    help="derive n_slots from this cache budget per dtype "
                         "(per tier in --tiers mode)")
    ap.add_argument("--out-dir", default=None,
                    help="write one JSON per sweep point here")
    ap.add_argument("--trace-dir", default=None,
                    help="write per-point observability artifacts here: "
                         "Chrome trace (.trace.json, open in Perfetto), "
                         "Prometheus exposition (.metrics.txt) and registry "
                         "snapshots (.metrics.jsonl) — DESIGN.md §13")
    ap.add_argument("--hlo-cost", action="store_true",
                    help="also report trip-count-aware FLOP/byte counts of "
                         "the compiled decode step per pool "
                         "(launch/hlo_analysis.py; offline lowering)")
    ap.add_argument("--dp", type=int, default=1,
                    help="data-parallel mesh axis (pool slots shard here)")
    ap.add_argument("--tp", type=int, default=1,
                    help="model-parallel mesh axis (weights/heads)")
    ap.add_argument("--force-host-devices", type=int, default=0,
                    help="CPU validation: fake this many host devices")
    args = ap.parse_args()

    force_host_devices(args.force_host_devices)
    import jax
    enable_compile_cache()
    from repro.configs import get_config
    from repro.models.common import QuantMaker
    from repro.models import transformer as T

    mesh = serving_mesh(args.dp, args.tp)

    cfg = get_config(args.arch, smoke=not args.full)
    if args.d_head:
        import dataclasses as _dc
        cfg = _dc.replace(cfg, d_head=args.d_head)
    print(f"== {cfg.name}: {cfg.n_layers}L d={cfg.d_model} ({cfg.family}); "
          f"schemes proj={cfg.scheme_proj} ffn={cfg.scheme_ffn}"
          + (f"; d_head={cfg.head_dim}" if args.d_head else "")
          + (f"; mesh dp={args.dp} x tp={args.tp}" if mesh is not None else ""))
    params = T.build_params(cfg, QuantMaker(jax.random.PRNGKey(0)))

    policy = None
    if args.policy:
        from repro.quant.policy import PrecisionPolicy
        with open(args.policy) as f:
            policy = PrecisionPolicy.from_json(f.read())

    tiers = [t.strip() for t in args.tiers.split(",") if t.strip()] \
        if args.tiers else None
    if tiers:
        sweep = [tiers[0]]               # one mixed point, default tier first
    elif policy is not None and args.kv_dtype == "bf16":
        sweep = [policy.kv]              # the policy's tier, unless swept
    else:
        sweep = [d.strip() for d in args.kv_dtype.split(",") if d.strip()]

    reports = []
    for kv_dtype in sweep:
        injector, arm_fault = build_fault_injector(args)
        engine = build_engine(args, cfg, params, kv_dtype, mesh, policy,
                              fault_injector=injector)
        rep = run_point(args, cfg, engine, kv_dtype, tiers=tiers,
                        arm_fault=arm_fault)
        label = "+".join(tiers) if tiers else kv_dtype
        print(f"\n== serving metrics [{label}]")
        print(json.dumps(rep, indent=2))
        if args.out_dir:
            os.makedirs(args.out_dir, exist_ok=True)
            path = os.path.join(
                args.out_dir,
                point_label(cfg, kv_dtype, tiers, args.max_burst,
                            args.weight_kernel, args.paged,
                            args=args) + ".json")
            with open(path, "w") as f:
                json.dump(rep, f, indent=2, allow_nan=False)
            print(f"== wrote {path}")
        reports.append(rep)

    if len(reports) > 1:
        print(f"\n== sweep summary ({cfg.name})")
        print(f"{'kv_dtype':>8} {'slots':>6} {'B/tok':>6} {'tok/s':>8} "
              f"{'disp/tok':>9} {'ttft_p50':>9} {'occupancy':>10}")
        for r in reports:
            # missing/null fields print as '-' (reports are NaN-free JSON)
            print(f"{r['kv_dtype']:>8} {r['n_slots']:>6} "
                  f"{r['kv_bytes_per_token']:>6} "
                  f"{str(r.get('tokens_per_s') or '-'):>8} "
                  f"{str(r.get('decode_dispatches_per_token', '-')):>9} "
                  f"{str(r.get('ttft_p50_s', '-')):>9} "
                  f"{r['slot_occupancy_mean']:>10}")

    if args.baseline_json:
        # append semantics: each invocation adds one sweep, so a multi-
        # regime baseline (e.g. benchmarks/BENCH_serve_baseline.json) is
        # reproduced by re-running the recorded bench_args command lines
        # against the same path
        sweep = {"bench_args": {k: v for k, v in vars(args).items()
                                if not k.startswith("_")},
                 "points": reports}
        payload = {"generated_by": "benchmarks/serve_bench.py",
                   "arch": cfg.name, "sweeps": []}
        if os.path.exists(args.baseline_json):
            with open(args.baseline_json) as f:
                payload = json.load(f)
        payload["sweeps"].append(sweep)
        d = os.path.dirname(args.baseline_json)
        if d:
            os.makedirs(d, exist_ok=True)
        with open(args.baseline_json, "w") as f:
            json.dump(payload, f, indent=2)
        print(f"== wrote {args.baseline_json} "
              f"({len(payload['sweeps'])} sweeps)")


if __name__ == "__main__":
    main()
