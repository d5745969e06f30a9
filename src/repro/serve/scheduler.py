"""Continuous-batching scheduler: FCFS admission over slot-based KV pools.

Each ``step()`` does up to three things, all against statically-shaped
jitted engine primitives (DESIGN.md §7, §11):

  1. **Admission** — FCFS per tier: the waiting queue is scanned in
     arrival order and a request is admitted as soon as a KV slot is free
     in *its tier's* pool.  Requests can join at any time, including
     mid-flight between decode steps.  With one tier (the default) this is
     exactly head-of-queue FCFS.
  2. **One prefill chunk** — the oldest PREFILL request advances by one
     fixed-size chunk (chunked prefill *interleaved* with decode, so a long
     prompt never stalls in-flight decodes for more than a chunk).  When
     the prompt completes, its first token is sampled from the chunk
     logits — that token is the request's TTFT event.
  3. **One decode round per tier** — every DECODE-state slot advances.
     Rows are cohorted by KV tier (each tier owns one pool, and a decode
     dispatch operates on one pool), so a mixed bf16/int8/fp8 workload
     issues one dispatch per active tier per round.  Each cohort's round
     is a planned **burst** of K token-steps executed as one jitted
     ``lax.scan`` on device (K = 1 falls back to the fused single step):
     one dispatch and one host sync per K generated tokens instead of per
     token.  K is the min over the cohort's slots of tokens-until-that-
     slot's next scheduling event (length/capacity retirement), clamped to
     1 whenever the waiting queue is non-empty or a prefill is mid-flight —
     so admission latency and chunked-prefill interleaving are byte-
     identical to a burst-free scheduler — and rounded down to a power of
     two so at most log2(max_burst) burst lengths ever compile.  EOS cannot
     be planned for; rows that sample it freeze mid-burst on device.

Retirement (EOS / max-new-tokens / slot capacity) frees the slot
immediately, so the next ``step()`` can admit a waiting request into it —
finished rows never burn decode steps, which is precisely what the old
static-batch ``generate()`` got wrong.

**Precision tiers** (DESIGN.md §12): ``Scheduler(engine, tiers=...)``
builds one pool per KV tier ('bf16' / 'int8' / 'fp8') and requests pick
theirs via ``Request.kv_policy`` — per-request runtime precision
switching inside one engine.  Concurrency is capped per pool, and each
pool is capped by KV bytes per token at ITS tier: with a
``cache_budget_bytes`` the int8/fp8 tiers admit roughly twice the slots
of the bf16 tier from the same budget (DESIGN.md §9), so the tier knob
is a per-request quality/capacity trade served from one engine.  The
scheduling logic itself is storage-agnostic — it sees alloc/free/lengths
per pool, and a request's computation touches only its own tier's slab,
so traffic at other tiers cannot perturb its tokens.

**Observability** (DESIGN.md §13): every round marks its phases with
``repro.obs.span`` — ``serve.step`` around the round, ``serve.admit``,
``serve.prefill``, ``serve.decode`` and ``serve.emit`` inside it (and the
engine's ``serve.sync`` around each blocking device->host read) — which
land in a ``jax.profiler`` trace on the device's clock.
``Scheduler(engine, obs=...)`` attaches a ``repro.obs.Observability``
bundle — a Chrome-trace tracer (per-request lifecycle spans, the same
spans' per-dispatch prefill/burst events, queue/slot counter tracks) and a
metrics registry (the scheduler publishes gauges and counters;
``ServeMetrics`` consumes the same registry).  All Chrome timestamps come
from the scheduler's injectable clock, so two virtual-clock runs produce
byte-identical trace files.  ``obs=None`` (default) adds nothing the loop
can observe: zero extra clock calls, zero extra host syncs, zero extra
dispatches (pinned by tests/test_obs.py).

**SLO-aware scheduling** (DESIGN.md §16): requests carry a priority class
(smaller = more important) and optional TTFT / e2e deadlines.  Admission
scans the queue in priority-then-arrival order (stable: with one class it
IS the FCFS scan), and when a waiter cannot be admitted the scheduler may
**preempt** the lowest-priority DECODE slot of its tier: the victim's
slot is freed (on a paged pool its registered prompt pages stay alive in
the prefix cache), and the victim is requeued with a ``resume_prompt`` —
prompt + all generated tokens but the last.  Re-admission re-prefills
only the tail past the prefix hit, emits nothing for the replayed tokens,
and decode continues at the preserved ``n_generated`` — so, with the
per-(request, step) key schedule, a preempted-then-resumed request's
output is bit-identical to an unpreempted run (pinned in
tests/test_slo_serving.py for slab and paged pools, single-device and
dp x tp).  ``Scheduler(engine, slo=...)`` attaches a ``serve.slo.SLOPolicy``
for admission control (typed rejections), KV-tier downgrade with
hysteresis, and cost-model burst/chunk planning.  Deadlines are enforced
step-granularly from the clock sample each round already takes.

**Fault tolerance** (DESIGN.md §16): every engine dispatch is fenced — a
``StepFault`` (killed dispatch, lost shard, or the ``ServeConfig
(fault_injector=...)`` test hook) or poisoned decode output (sampled ids
outside the vocabulary) invalidates the affected slots and requeues their
requests through the same preempt-and-resume path, with bounded
retry-and-backoff (``ServeConfig.max_fault_retries``, exponential hold in
scheduler steps) instead of process death.  A request that exhausts its
budget retires with ``finish_reason='fault'``.  Because a faulted
dispatch's outputs are dropped whole and recovery replays from the KV
recompute, fault recovery preserves the bit-identity contract.

Determinism: sampling keys are per (request, step) — see request.py — and
row computations are independent of batch composition (dense ops are
row-wise; MoE decode routes each row as its own drop-free single-token
group), so a request's greedy output is identical whether it was served
alone, in a full one-shot batch, admitted mid-flight next to strangers,
advanced K tokens at a time inside a burst, cohorted beside other tiers,
or preempted and resumed.  The clock is injectable for metric tests.
Burst timing caveat: all K tokens of a burst surface at burst end, so
their ``token_times`` are burst-granular (see metrics.py).
"""
from __future__ import annotations

import time
from collections import deque
from typing import Callable, Deque, Dict, List, Mapping, Optional, Sequence, \
    Tuple, Union

import numpy as np

from repro.obs.span import span
from repro.obs.trace import PID_REQUESTS, PID_SCHEDULER
from repro.runtime.fault_tolerance import RetryBudget, StepFault

from .kv_pool import KVCachePool
from .metrics import ServeMetrics
from .request import Request, RequestState, SamplingParams  # noqa: F401
from .sampling import (batched_step_keys, sample_one,  # noqa: F401
                       sample_tokens)
from .spec import DraftEngine, SpecConfig, SpecPlanner, accept_longest_prefix


class Scheduler:
    def __init__(self, engine, *, pool: Optional[KVCachePool] = None,
                 clock: Callable[[], float] = time.monotonic,
                 max_burst: Optional[int] = None,
                 tiers: Union[None, Sequence[str],
                              Mapping[str, Optional[int]]] = None,
                 obs=None, slo=None,
                 spec: Optional[SpecConfig] = None):
        """``tiers``: KV tiers this scheduler serves — a sequence of tier
        names (each pool sized by the engine's ServeConfig: explicit
        ``n_slots`` or budget-derived per tier) or a {tier: n_slots}
        mapping (None values fall back to the config sizing).  Default:
        one pool at the engine policy's tier.  ``pool`` injects a single
        pre-built pool instead (mutually exclusive with ``tiers``).
        ``obs``: a ``repro.obs.Observability`` bundle (tracer / registry /
        snapshot writer, each optional); None disables all of it at zero
        cost.  ``slo``: a ``serve.slo.SLOPolicy`` — admission
        control, KV-tier downgrade with hysteresis, and cost-model burst/
        chunk planning (DESIGN.md §16); None keeps the policy-free
        admit-everything scheduler.  ``spec``: a ``serve.spec.SpecConfig``
        — speculative decoding with low-precision drafts (DESIGN.md §17):
        eligible decode rounds draft K tokens per row on a cheap twin of
        the engine and verify the whole window in one target dispatch;
        accepted tokens stay bit-identical to non-speculative decode.
        None (default) changes nothing."""
        self.engine = engine
        if pool is not None and tiers is not None:
            raise ValueError("give either pool= or tiers=, not both")
        if pool is not None:
            # an injected pool must be chunk-aligned, or a final-chunk write
            # window past ``capacity`` gets clamp-shifted by
            # dynamic_update_slice onto committed positions (silent KV
            # corruption) — engine.new_pool() aligns automatically
            C = engine.scfg.prefill_chunk
            need = -(-pool.max_len // C) * C
            if pool.capacity < need:
                raise ValueError(
                    f"pool capacity {pool.capacity} not aligned to prefill "
                    f"chunk {C} (need >= {need}); build it with "
                    f"engine.new_pool() or KVCachePool(..., align={C})")
            self.pools: Dict[str, KVCachePool] = {pool.kv_dtype: pool}
        elif tiers is not None:
            items = list(tiers.items()) if isinstance(tiers, Mapping) \
                else [(t, None) for t in tiers]
            if not items:
                raise ValueError("tiers= must name at least one KV tier")
            self.pools = {}
            for tier, n in items:
                p = engine.new_pool(n_slots=n, kv_dtype=tier)
                if p.kv_dtype in self.pools:
                    raise ValueError(f"duplicate KV tier {p.kv_dtype!r}")
                self.pools[p.kv_dtype] = p
        else:
            p = engine.new_pool()
            self.pools = {p.kv_dtype: p}
        # requests that don't ask for a tier (kv_policy=None) land here:
        # the engine policy's tier when served, else the first tier listed
        default = engine.scfg.kv_dtype
        self.default_tier = default if default in self.pools \
            else next(iter(self.pools))
        # burst cap: ServeConfig.max_burst unless overridden per scheduler
        self.max_burst = int(getattr(engine.scfg, "max_burst", 1)
                             if max_burst is None else max_burst)
        assert self.max_burst >= 1
        self.waiting: Deque[Request] = deque()
        self.running: Dict[Tuple[str, int], Request] = {}  # (tier, slot)
        self.finished: List[Request] = []
        self.slo = slo
        # fault tolerance (DESIGN.md §16): bounded per-request retry with
        # exponential backoff; the poisoned-output guard (sampled ids in
        # [0, vocab)) is armed only when a fault injector is — a real
        # deployment would arm an isfinite guard the same way
        self._retry = RetryBudget(
            getattr(engine.scfg, "max_fault_retries", 3))
        self._ft_check = getattr(engine.scfg, "fault_injector",
                                 None) is not None
        # freshest known clock sample (stamped once per step and at every
        # submit) — deadline shedding reads THIS instead of taking extra
        # clock calls, keeping the obs-disabled zero-extra-calls contract
        self._last_now: Optional[float] = None
        self.obs = obs
        self.tracer = obs.tracer if obs is not None else None
        # speculative decoding (DESIGN.md §17): the draft twin and its
        # K-controller exist only when asked for — spec=None adds zero
        # state, zero dispatches, zero trace events
        self.spec_cfg = spec
        self.draft = DraftEngine(engine, spec) if spec is not None else None
        self.spec_planner = SpecPlanner(spec) if spec is not None else None
        # stable Perfetto lane per tier on the scheduler process: tid 0 is
        # the prefill lane, decode tiers get 1.. in sorted order; with
        # speculation enabled each tier additionally gets a draft and a
        # verify lane past the decode block (registered ONLY then, so
        # spec-off trace files stay byte-identical)
        self._tier_tid = {t: 1 + i for i, t in enumerate(sorted(self.pools))}
        base = 1 + len(self.pools)
        self._spec_tid = {t: (base + 2 * i, base + 2 * i + 1)
                          for i, t in enumerate(sorted(self.pools))}
        if self.tracer is not None:
            self.tracer.process_name(PID_REQUESTS, "requests")
            self.tracer.process_name(PID_SCHEDULER, "scheduler")
            self.tracer.thread_name(PID_SCHEDULER, 0, "prefill")
            for t, tid in sorted(self._tier_tid.items()):
                self.tracer.thread_name(PID_SCHEDULER, tid, f"decode:{t}")
            if spec is not None:
                for t, (dtid, vtid) in sorted(self._spec_tid.items()):
                    self.tracer.thread_name(PID_SCHEDULER, dtid, f"draft:{t}")
                    self.tracer.thread_name(PID_SCHEDULER, vtid,
                                            f"verify:{t}")
        registry = obs.registry if obs is not None else None
        self._r_steps = self._r_queue = self._r_used = None
        self._r_adm = self._r_chunks = self._r_syncs = None
        self._r_hits = self._r_hit_tokens = self._r_pages = None
        self._syncs_published = 0
        self._any_paged = any(getattr(p, "paged", False)
                              for p in self.pools.values())
        if registry is not None:
            self._r_steps = registry.counter(
                "serve_scheduler_steps_total", "scheduling rounds")
            self._r_queue = registry.gauge(
                "serve_queue_depth", "requests WAITING for a KV slot")
            self._r_used = registry.gauge(
                "serve_slots_used", "occupied KV slots, by tier")
            slots_total = registry.gauge(
                "serve_slots_total", "provisioned KV slots, by tier")
            for t, p in sorted(self.pools.items()):
                slots_total.set(p.n_slots, tier=t)
            self._r_adm = registry.counter(
                "serve_admissions_total",
                "WAITING -> PREFILL transitions, by tier")
            self._r_chunks = registry.counter(
                "serve_prefill_chunks_total",
                "prefill chunk dispatches, by tier")
            self._r_syncs = registry.counter(
                "serve_host_syncs_total",
                "blocking device->host transfers on the serving hot path")
            if self._any_paged:
                self._r_hits = registry.counter(
                    "serve_prefix_hits_total",
                    "admissions that adopted cached prefix pages, by tier")
                self._r_hit_tokens = registry.counter(
                    "serve_prefix_hit_tokens_total",
                    "prompt tokens served from the prefix cache, by tier")
                self._r_pages = registry.gauge(
                    "serve_pages",
                    "page-arena occupancy, by tier and state "
                    "(used / cached / free)")
        self.metrics = ServeMetrics(
            sum(p.n_slots for p in self.pools.values()), registry=registry)
        if len(self.pools) > 1:
            self.metrics.tiers = {t: p.n_slots
                                  for t, p in self.pools.items()}
        # sharded serving is invisible to the scheduling logic (the pool
        # interface is identical), but the mesh shape belongs in reports
        self.metrics.topology = getattr(engine, "topology", None)
        self._clock = clock
        self._next_id = 0
        self.n_steps = 0
        # monotone engine-dispatch id (prefill chunks and decode rounds
        # share the sequence); stamped on every emitted token so the
        # burst-spread ITL estimate and the tracer can attribute tokens
        # to the dispatch that surfaced them.  Advances identically with
        # obs on or off.
        self._dispatch_seq = 0
        # device->host blocking transfers on the serving hot path: final
        # prefill-chunk logits, the first-token sample, one per decode
        # dispatch, and one per key-schedule build (temperature rows,
        # batched across rows)
        self.n_host_syncs = 0

    @property
    def pool(self) -> KVCachePool:
        """The default tier's pool (single-tier callers' interface)."""
        return self.pools[self.default_tier]

    @property
    def n_decode_steps(self) -> int:
        """Decode TOKEN-steps executed (a burst adds its planned K)."""
        return self.metrics.decode_token_steps

    @property
    def n_decode_dispatches(self) -> int:
        """Jitted decode/burst entries (one per tier cohort per round)."""
        return self.metrics.decode_dispatches

    # ------------------------------------------------------------------
    def _resolve_tier(self, req: Request) -> KVCachePool:
        """Resolve and validate the request's KV tier (eagerly, with an
        actionable message) and its end-to-end slot fit."""
        tier = self.default_tier if req.kv_policy is None else req.kv_policy
        if tier not in self.pools:
            raise ValueError(
                f"request kv_policy={req.kv_policy!r}: no pool at that "
                f"tier; this scheduler serves {sorted(self.pools)} "
                "(build it with Scheduler(engine, tiers=[...]))")
        req.tier = tier
        pool = self.pools[tier]
        need = req.prompt_len + req.sampling.max_new_tokens
        if need > pool.max_len:
            raise ValueError(
                f"request needs {need} cache positions "
                f"(prompt {req.prompt_len} + max_new "
                f"{req.sampling.max_new_tokens}) > slot capacity "
                f"{pool.max_len}")
        return pool

    def submit(self, req: Request) -> Request:
        """Enqueue (priority-then-arrival order is applied at admission;
        with one priority class this is exactly FCFS).  With an SLO
        policy attached, the request may be DOWNGRADED to a denser KV
        tier (``req.downgraded_from`` records the original) or shed with
        a typed verdict: it comes back FINISHED with
        ``finish_reason='rejected'`` and ``req.rejection`` set, and is
        never enqueued — callers must check ``is_finished`` when serving
        under a policy."""
        self._resolve_tier(req)
        if req.id is None:
            req.id = self._next_id
        self._next_id = max(self._next_id, req.id) + 1
        req.state = RequestState.WAITING
        req.arrival_time = self._clock()
        self._last_now = req.arrival_time
        req.last_enqueue_time = req.arrival_time
        self.metrics.on_arrival(req.arrival_time)
        if self.slo is not None:
            verdict = self.slo.admit(req, self)
            if req.downgraded_from is not None \
                    and req.tier != req.kv_policy:
                # the policy downgraded the tier in place — re-resolve
                # (and re-validate the fit at the denser tier)
                self._resolve_tier(req)
                self.metrics.on_downgrade(req)
            if verdict is not None:
                req.rejection = verdict
                self.metrics.on_reject(req)
                self._finish_unadmitted(req, "rejected",
                                        req.arrival_time, None)
                return req
        self.waiting.append(req)
        return req

    @property
    def has_work(self) -> bool:
        return bool(self.waiting or self.running)

    @property
    def kv_bytes_per_token(self) -> int:
        """Cache bytes one committed position costs at the default tier
        (pool storage dtype included) — the denominator of the
        slots-per-budget trade."""
        return self.pool.bytes_per_token

    # ------------------------------------------------------------------
    def _plan_burst(self, dec: List[Request], pool: KVCachePool) -> int:
        """Burst length K for one tier cohort's round (DESIGN.md §11).

        K = min over the cohort's rows of the tokens that row can still
        emit before a *predictable* scheduling event — its max-new-tokens
        budget or its slot-capacity horizon — capped by ``max_burst`` and
        rounded DOWN to a power of two (bounds compiled burst variants;
        correctness never depends on the plan, only efficiency).  Clamped
        to 1 whenever admission could happen next round (waiting queue
        non-empty, any tier) or a prefill is mid-flight, so burst mode
        changes neither admission latency nor prefill/decode interleaving.
        EOS is unplannable and is handled by the on-device stop masks
        instead."""
        if self.max_burst <= 1 or self.waiting:
            return 1
        if any(r.state is RequestState.PREFILL
               for r in self.running.values()):
            return 1
        k = self.max_burst
        if self.slo is not None:
            # cost-model cap: largest K whose modeled wall fits the
            # policy's per-round latency budget (DESIGN.md §16)
            k = self.slo.burst_cap(self, dec, pool, k)
        for r in dec:
            budget = r.sampling.max_new_tokens - r.n_generated
            capacity = pool.max_len - int(pool.lengths[r.slot]) - 1
            k = min(k, max(1, min(budget, capacity)))
        return 1 << (k.bit_length() - 1)   # largest power of two <= k

    def step(self) -> Dict[str, List]:
        """One scheduling round.  Returns the tokens emitted this round
        (``emitted``: list of (request, slot, token)) and requests retired
        (``finished``)."""
        with span("serve.step", step=self.n_steps):
            return self._step()

    def _step(self) -> Dict[str, List]:
        emitted: List = []
        finished_now: List[Request] = []

        # 0. deadline shedding (step-granular, from the freshest clock
        # sample already taken): WAITING requests whose TTFT or e2e
        # deadline has already passed can no longer meet their SLO — shed
        # them before they cost a slot
        self._shed_expired_waiting(finished_now)

        # 1. admission: priority-then-deadline-then-arrival scan (EDF
        # within a priority class: requests carrying a TTFT deadline sort
        # by its absolute wall time, deadline-free requests after them;
        # the sort is stable, so with no deadlines set one class is
        # exactly the FCFS scan); a request is admitted when its tier's
        # pool has a free slot (paged: slot AND pages).  When it cannot
        # be admitted and a strictly lower-priority DECODE slot exists in
        # its tier, that victim is PREEMPTED: slot freed (registered
        # prompt pages stay in the prefix cache), request requeued with a
        # resume buffer (DESIGN.md §16).  The scan early-exits once no
        # waiter could be admitted even by preemption: the scan order is
        # priority-sorted, so the first hopeless waiter proves the rest
        # hopeless too — a backlogged queue stays O(sort) per step.
        admitted: List[Request] = []
        if self.waiting:
            with span("serve.admit", waiting=len(self.waiting)) as sp:
                self._admit(admitted)
                sp.set(admitted=len(admitted))

        # 2. prefill chunks for the oldest mid-prefill request (one per
        # round unless the SLO policy budgets more from the cost model)
        n_chunks = 1 if self.slo is None \
            else self.slo.prefill_chunks_per_step(self)
        for _ in range(n_chunks):
            if not self._prefill_one_chunk(emitted, finished_now):
                break

        # 3. one decode round (burst of K token-steps) per tier cohort —
        # or, with speculation enabled and the same conditions under
        # which bursts plan K > 1 (nothing waiting, no prefill
        # mid-flight), a speculative draft/verify round (DESIGN.md §17)
        dec = sorted((r for r in self.running.values()
                      if r.state is RequestState.DECODE), key=lambda r: r.id)
        spec_ok = (self.spec_planner is not None and not self.waiting
                   and not any(r.state is RequestState.PREFILL
                               for r in self.running.values()))
        for tier in sorted({r.tier for r in dec}):
            cohort = [r for r in dec if r.tier == tier]
            pool = self.pools[tier]
            if spec_ok:
                ks = self.spec_planner.plan([(r, r.slot) for r in cohort],
                                            pool)
                if ks >= 1:
                    self._decode_spec(cohort, pool, ks, emitted,
                                      finished_now)
                    continue
            k = self._plan_burst(cohort, pool)
            if k <= 1:
                self._decode_single(cohort, pool, emitted, finished_now)
            else:
                self._decode_burst(cohort, pool, k, emitted, finished_now)

        self.n_steps += 1
        now = self._clock()
        self._last_now = now
        # queue-wait stamps for this round's admissions (the tracer path
        # stamped precisely at admission; everyone else gets the round's
        # clock sample — zero extra clock calls either way)
        for req in admitted:
            if req.admit_time is None:
                req.admit_time = now
            self.metrics.on_admit(req)
        # e2e deadline enforcement for running requests (step-granular)
        for req in [r for r in self.running.values()
                    if r.e2e_deadline_s is not None
                    and r.arrival_time is not None
                    and now - r.arrival_time > r.e2e_deadline_s]:
            self._retire(req, "deadline_exceeded", now, finished_now)
        self.metrics.on_step(
            now, {t: p.n_used for t, p in self.pools.items()})
        if self.obs is not None:
            self._obs_step(now)
        return {"emitted": emitted, "finished": finished_now}

    # ------------------------------------------------------------------
    # Admission, preemption, deadline shedding (DESIGN.md §16)
    # ------------------------------------------------------------------
    def _admit(self, admitted: List[Request]) -> None:
        """The admission scan (see ``step``): appends each admitted
        request to ``admitted`` and takes it off the waiting queue."""
        free_total = sum(p.n_free for p in self.pools.values())
        order = sorted(self.waiting, key=self._admit_order_key)
        run_prios = [r.priority for r in self.running.values()
                     if r.state is RequestState.DECODE]
        max_run_prio = max(run_prios) if run_prios else None
        for req in order:
            if req.hold_until_step > self.n_steps:
                continue           # fault backoff: not yet retryable
            if free_total == 0 and (max_run_prio is None
                                    or req.priority >= max_run_prio):
                break              # neither a slot nor a victim
            if self._try_admit(req):
                admitted.append(req)
                free_total = sum(p.n_free for p in self.pools.values())
                run_prios = [r.priority for r in self.running.values()
                             if r.state is RequestState.DECODE]
                max_run_prio = max(run_prios) if run_prios else None
        if admitted:
            gone = {id(r) for r in admitted}
            self.waiting = deque(r for r in self.waiting
                                 if id(r) not in gone)

    @staticmethod
    def _admit_order_key(r: Request) -> Tuple[int, float]:
        """Admission scan order: priority class first, then EDF within
        the class — the ABSOLUTE TTFT deadline (arrival + relative
        deadline), with deadline-free requests after every deadline
        carrier.  The sort is stable, so arrival order breaks ties and a
        deadline-free single-class queue is exactly FCFS."""
        if r.ttft_deadline_s is None:
            return (r.priority, float("inf"))
        return (r.priority, (r.arrival_time or 0.0) + r.ttft_deadline_s)

    def _try_admit(self, req: Request) -> bool:
        """Admit ``req`` into its tier's pool, preempting lower-priority
        DECODE slots of that tier if needed (and possible).  On success
        the request is PREFILL-state and registered in ``running``."""
        pool = self.pools[req.tier]
        max_new = req.sampling.max_new_tokens - max(req.n_generated - 1, 0)
        while True:
            # a slot (paged pools, DESIGN.md §15: AND enough arena pages
            # for the request's worst-case growth; a prefix-cache hit
            # adopts shared pages and resumes prefill past them — which
            # is what makes a preempted request's resume re-prefill only
            # its generated tail)
            adm = pool.admit(req.prefill_tokens, max_new)
            if adm is not None:
                req.slot, req.prefill_pos, req.prefix_hit_tokens = adm
                break
            victim = self._pick_victim(req.tier, req.priority)
            if victim is None:
                return False
            self._preempt(victim, reason="priority")
        if self._r_hits is not None and req.prefix_hit_tokens > 0:
            self._r_hits.inc(tier=req.tier)
            self._r_hit_tokens.inc(req.prefix_hit_tokens, tier=req.tier)
        req.state = RequestState.PREFILL
        # one-time prompt pre-pass: int32 + chunk padding hoisted out of
        # the per-chunk loop (engine slices views from it); rebuilt after
        # a preemption because the resume buffer replaced the prompt
        if req.prompt_padded is None:
            req.prompt_padded, _ = self.engine.pad_prompt(
                req.prefill_tokens)
        self.running[(req.tier, req.slot)] = req
        # admit stamp feeds the WAITING span; gated so the disabled path
        # makes zero extra clock calls
        if self.tracer is not None:
            req.admit_time = self._clock()
        if self._r_adm is not None:
            self._r_adm.inc(tier=req.tier)
        return True

    def _pick_victim(self, tier: str,
                     priority: int) -> Optional[Request]:
        """The DECODE request of ``tier`` to evict for a priority-
        ``priority`` waiter: strictly lower class only (never preempt an
        equal — that would livelock two requests trading one slot), the
        lowest class first, and among equals the one with the least
        generated output (cheapest KV recompute), then the youngest."""
        cands = [r for r in self.running.values()
                 if r.tier == tier and r.state is RequestState.DECODE
                 and r.priority > priority]
        if not cands:
            return None
        return max(cands,
                   key=lambda r: (r.priority, -r.n_generated, r.id))

    def _preempt(self, req: Request, reason: str = "priority") -> None:
        """Evict ``req`` from its slot and requeue it WAITING with a
        resume buffer: the original prompt plus every generated token but
        the last (the last token is the next decode INPUT — its KV was
        never written).  The slot's pages are freed; on a paged pool the
        registered prompt pages stay alive in the prefix cache, so
        re-admission prefix-hits them and re-prefills only the generated
        tail.  ``n_generated`` and the output are preserved, which (with
        per-(request, step) keys) makes the resumed continuation
        bit-identical to an unpreempted run."""
        assert req.state in (RequestState.PREFILL, RequestState.DECODE)
        del self.running[(req.tier, req.slot)]
        if self.draft is not None:
            # mirrored draft-KV state is slot-keyed: stale the moment the
            # target slot is freed (re-admission catches up from the
            # request's own committed tokens)
            self.draft.release(req.tier, req.slot)
        self.pools[req.tier].free(req.slot)
        req.slot = None
        req.state = RequestState.WAITING
        if req.output_tokens:
            req.resume_prompt = np.concatenate(
                [req.prompt,
                 np.asarray(req.output_tokens[:-1], np.int32)]) \
                if req.n_generated > 1 else req.prompt
        req.prompt_padded = None
        req.prefill_pos = 0
        req.prefix_hit_tokens = 0
        req.n_preemptions += 1
        req.last_enqueue_time = self._last_now
        req.admit_time = None
        self.waiting.append(req)
        self.metrics.on_preempt(req, reason=reason)
        if self.tracer is not None and self._last_now is not None:
            self.tracer.instant(
                "preempted", self._last_now, pid=PID_REQUESTS,
                tid=req.id or 0,
                args={"reason": reason, "n_generated": req.n_generated,
                      "n_preemptions": req.n_preemptions})

    def _shed_expired_waiting(self, finished_now: List[Request]) -> None:
        """Retire WAITING requests whose TTFT or e2e deadline already
        passed (they can no longer meet their SLO; holding them only
        starves feasible work).  Uses the freshest existing clock sample
        — no extra clock calls on the disabled-obs path."""
        now = self._last_now
        if now is None or not self.waiting:
            return
        expired = [
            r for r in self.waiting
            if r.arrival_time is not None
            and ((r.ttft_deadline_s is not None
                  and r.first_token_time is None
                  and now - r.arrival_time > r.ttft_deadline_s)
                 or (r.e2e_deadline_s is not None
                     and now - r.arrival_time > r.e2e_deadline_s))]
        if not expired:
            return
        gone = {id(r) for r in expired}
        self.waiting = deque(r for r in self.waiting
                             if id(r) not in gone)
        for r in expired:
            self._finish_unadmitted(r, "deadline_exceeded", now,
                                    finished_now)

    def _finish_unadmitted(self, req: Request, reason: str,
                           now: float,
                           finished_now: Optional[List[Request]]) -> None:
        """Retire a request that holds no slot (rejected at submit, or
        shed from the WAITING queue)."""
        req.state = RequestState.FINISHED
        req.finish_reason = reason
        req.finish_time = now
        self.finished.append(req)
        if finished_now is not None:
            finished_now.append(req)
        self.metrics.on_finish(req)
        if self.tracer is not None:
            self._trace_request(req)

    # ------------------------------------------------------------------
    # Fault recovery (DESIGN.md §16)
    # ------------------------------------------------------------------
    def _on_fault(self, cohort: List[Request], fault: StepFault,
                  finished_now: List[Request]) -> None:
        """One engine dispatch died or returned poisoned output: drop the
        dispatch's outputs whole, invalidate every affected slot, and
        requeue the requests through the preempt-and-resume path (their
        KV is recomputed, so the recovery is bit-identical).  Each
        request's retry budget is charged; exhausted requests retire with
        ``finish_reason='fault'``; survivors are held back
        exponentially-longer each time (backoff in scheduler steps)."""
        self.metrics.on_fault(fault, len(cohort))
        for r in list(cohort):
            backoff = self._retry.record_fault(r.id)
            r.n_faults += 1
            if backoff is None:
                # budget exhausted: permanent retirement.  The slot is
                # still owned here — _retire frees it.
                now = self._last_now if self._last_now is not None \
                    else r.arrival_time
                self._retire(r, "fault", now, finished_now)
            else:
                r.hold_until_step = self.n_steps + backoff
                self._preempt(r, reason="fault")

    def _tokens_poisoned(self, toks: np.ndarray) -> bool:
        """Poisoned-output guard (armed only with a fault injector, like
        a deployment's isfinite guard): sampled ids must be valid vocab
        entries."""
        return bool(np.any((toks < 0) | (toks >= self.engine.cfg.vocab)))

    def _prefill_one_chunk(self, emitted: List,
                           finished_now: List[Request]) -> bool:
        """One prefill-chunk dispatch for the oldest mid-prefill request;
        returns False when there was nothing to prefill or the served
        request finished its prompt (callers budgeting several chunks per
        round stop there).  Serves the resume buffer after a preemption —
        the final chunk of a resume emits NOTHING (those tokens were
        already delivered; only their KV needed recomputing)."""
        pre = [r for r in self.running.values()
               if r.state is RequestState.PREFILL]
        if not pre:
            return False
        req = min(pre, key=lambda r: r.id)
        pool = self.pools[req.tier]
        self._dispatch_seq += 1
        start = req.prefill_pos
        plen = req.prefill_len
        C = self.engine.scfg.prefill_chunk
        end = min(start + C, plen)
        final = end >= plen
        resumed = req.is_resuming
        with span("serve.prefill", self.tracer, self._clock,
                  event="prefill_chunk", pid=PID_SCHEDULER, tid=0,
                  req=req.id, tier=req.tier, pos=start, tokens=end - start,
                  final=final, dispatch=self._dispatch_seq):
            try:
                chunk_logits = self.engine.prefill_chunk_into_slot(
                    pool, req.slot, req.prompt_padded, start,
                    prompt_len=plen, need_logits=not resumed)
            except StepFault as f:
                self._on_fault([req], f, finished_now)
                return False
            req.prefill_pos = end
            if final:
                req.state = RequestState.DECODE
                # publish the committed whole pages to the prefix cache
                # (paged pools) — later requests (or this one, preempted
                # again) with the same token prefix adopt them instead
                # of re-prefilling
                pool.register_prefix(req.slot, req.prefill_tokens)
                if resumed:
                    # replay complete: KV now covers prompt +
                    # generated[:-1]; decode continues at the preserved
                    # n_generated with output_tokens[-1] as the next
                    # input.  No logits were computed, nothing crosses
                    # the host, nothing is emitted.
                    req.resume_prompt = None
                else:
                    # two blocking transfers: the final-chunk logits and
                    # the sampled first token
                    self.n_host_syncs += 2
                    tok = sample_one(chunk_logits[(plen - 1) % C],
                                     req.step_key(),
                                     req.sampling.temperature)
        if self._r_chunks is not None:
            self._r_chunks.inc(tier=req.tier)
        if final and not resumed:
            self._emit(req, tok, emitted, finished_now,
                       dispatch=self._dispatch_seq)
        return not final

    def _obs_step(self, now: float) -> None:
        """Post-round observability publication (obs-enabled path only):
        scheduler gauges/counters into the registry, queue/slot counter
        tracks into the trace, and the periodic snapshot tick."""
        if self._r_steps is not None:
            self._r_steps.inc()
            self._r_queue.set(len(self.waiting))
            for t, p in sorted(self.pools.items()):
                self._r_used.set(p.n_used, tier=t)
            # publish by delta so the counter stays monotone while
            # n_host_syncs remains the raw baseline-pinned tally
            self._r_syncs.inc(self.n_host_syncs - self._syncs_published)
            self._syncs_published = self.n_host_syncs
            if self._r_pages is not None:
                for t, p in sorted(self.pools.items()):
                    if getattr(p, "paged", False):
                        self._r_pages.set(p.pages_in_use, tier=t,
                                          state="used")
                        self._r_pages.set(p.pages_cached, tier=t,
                                          state="cached")
                        self._r_pages.set(p.pages_free, tier=t,
                                          state="free")
        if self.tracer is not None:
            self.tracer.counter("queue_depth", now,
                                {"waiting": len(self.waiting)})
            self.tracer.counter(
                "slots_used", now,
                {t: self.pools[t].n_used for t in sorted(self.pools)})
        self.obs.on_step(now)

    def _key_schedule(self, dec: List[Request], k: int,
                      keys: np.ndarray, temps: np.ndarray) -> None:
        """Fill the [k, n_slots, 2] ``keys`` schedule and [n_slots]
        ``temps`` for the temperature rows of ``dec`` — ONE batched
        computation and ONE blocking transfer for the whole round
        (greedy rows keep key 0; their key is never consumed)."""
        trows = [r for r in dec if r.sampling.temperature > 0]
        if not trows:
            return
        sched = batched_step_keys(
            [r.sampling.seed for r in trows], [r.id or 0 for r in trows],
            [r.n_generated for r in trows], k)          # [R, k, 2]
        self.n_host_syncs += 1
        for r, row in zip(trows, sched):
            temps[r.slot] = r.sampling.temperature
            keys[:, r.slot] = row

    def _decode_span(self, dec: List[Request], pool: KVCachePool,
                     k: int) -> span:
        """The ``serve.decode`` span of one tier cohort's round, and its
        ``decode_burst`` Chrome event on the tier's lane (the span's
        duration is the dispatch's host-visible wall, its blocking
        device->host transfer included)."""
        tier = pool.kv_dtype
        return span("serve.decode", self.tracer, self._clock,
                    event="decode_burst", pid=PID_SCHEDULER,
                    tid=self._tier_tid[tier], tier=tier, k=k, rows=len(dec),
                    slots=sorted(r.slot for r in dec),
                    dispatch=self._dispatch_seq)

    def _decode_single(self, dec: List[Request], pool: KVCachePool,
                       emitted: List, finished_now: List[Request]) -> None:
        """K = 1: one fused decode+sample step for one tier cohort
        (sampling still on device — only [n_slots] token ids cross to the
        host)."""
        n = pool.n_slots
        self._dispatch_seq += 1
        with self._decode_span(dec, pool, 1) as sp:
            tokens = np.zeros((n,), np.int32)
            keys = np.zeros((1, n, 2), np.uint32)    # inactive rows: key 0
            temps = np.zeros((n,), np.float32)
            for r in dec:
                tokens[r.slot] = r.last_token
            self._key_schedule(dec, 1, keys, temps)
            # pin every active row's write position (paged pools: a
            # fresh page at a page boundary) before the dispatch writes
            pool.ensure_decode([r.slot for r in dec], 1)
            try:
                toks = self.engine.decode_slots(pool, tokens, keys[0], temps)
            except StepFault as f:
                self._on_fault(dec, f, finished_now)
                return
            self.n_host_syncs += 1
            if self._ft_check \
                    and self._tokens_poisoned(toks[[r.slot for r in dec]]):
                self._on_fault(dec, StepFault("nan",
                                              "decode ids out of vocab"),
                               finished_now)
                return
            sp.set(emitted=len(dec))
        self.metrics.on_decode_burst(1, len(dec), tier=pool.kv_dtype)
        with span("serve.emit", tokens=len(dec)) as sp:
            n_done = len(finished_now)
            for r in dec:
                # the input token's KV was just written at lengths[slot]
                pool.lengths[r.slot] += 1
                self._emit(r, int(toks[r.slot]), emitted, finished_now,
                           dispatch=self._dispatch_seq)
            sp.set(finished=len(finished_now) - n_done)

    def _decode_burst(self, dec: List[Request], pool: KVCachePool, k: int,
                      emitted: List, finished_now: List[Request]) -> None:
        """K > 1: one device-resident burst for one tier cohort.  Emission
        replays the device's step-major order host-side, so `_emit`
        bookkeeping (retirement, slot free, metrics) sees exactly the
        sequence K single steps would have produced."""
        n = pool.n_slots
        self._dispatch_seq += 1
        with self._decode_span(dec, pool, k) as sp:
            tokens = np.zeros((n,), np.int32)
            temps = np.zeros((n,), np.float32)
            eos = np.full((n,), -1, np.int32)
            active = np.zeros((n,), bool)
            rem = np.zeros((n,), np.int32)
            keys = np.zeros((k, n, 2), np.uint32)
            for r in dec:
                tokens[r.slot] = r.last_token
                eos[r.slot] = r.sampling.eos_id
                active[r.slot] = True
                rem[r.slot] = r.sampling.max_new_tokens - r.n_generated
            self._key_schedule(dec, k, keys, temps)
            # pin the whole K-step write window per row, capped at each
            # row's remaining budget — on a paged pool, overshoot writes
            # from rows that freeze mid-burst land in the garbage page via
            # their unmapped table entries, not in allocated pages
            pool.ensure_decode([r.slot for r in dec], k,
                               [int(rem[r.slot]) for r in dec])
            try:
                toks, valid = self.engine.decode_burst(
                    pool, tokens, keys, temps, active, rem, eos)
            except StepFault as f:
                self._on_fault(dec, f, finished_now)
                return
            self.n_host_syncs += 1
            if self._ft_check and self._tokens_poisoned(toks[valid]):
                # the burst committed pool.lengths before the guard
                # tripped; preempt-and-requeue frees the slot (and its
                # pages), so the poisoned commits never reach a served
                # token
                self._on_fault(dec, StepFault("nan",
                                              "burst ids out of vocab"),
                               finished_now)
                return
            n_emit = int(valid.sum())
            sp.set(emitted=n_emit)
        self.metrics.on_decode_burst(k, n_emit, tier=pool.kv_dtype)
        # slots are captured before emission: _emit may retire a request
        # mid-replay (clearing req.slot), but its already-emitted burst
        # tokens are still addressed by the slot it occupied on device
        rows = [(r, r.slot) for r in dec]
        with span("serve.emit", tokens=n_emit) as sp:
            n_done = len(finished_now)
            for t in range(k):
                for r, slot in rows:
                    if valid[t, slot]:
                        # engine.decode_burst already committed lengths
                        self._emit(r, int(toks[t, slot]), emitted,
                                   finished_now, dispatch=self._dispatch_seq)
            sp.set(finished=len(finished_now) - n_done)

    def _decode_spec(self, dec: List[Request], pool: KVCachePool, k: int,
                     emitted: List, finished_now: List[Request]) -> None:
        """One speculative round for one tier cohort (DESIGN.md §17):
        draft K tokens per row on the DraftEngine's mirrored low-precision
        pool, verify the whole [last, d_1..d_K] window in ONE target
        dispatch, and emit the longest agreeing prefix plus the target's
        own next sample.  Every emitted token was sampled by the TARGET
        model with the request's real per-(id, n_generated) step key, so
        the output is bit-identical to non-speculative decode at any
        acceptance rate; a fully-rejected round still emits the verify's
        position-0 sample (exactly the plain step's token).  Three host
        syncs per round (key schedule, draft burst, verify) cover up to
        K+1 tokens per row."""
        tier = pool.kv_dtype
        n = pool.n_slots
        s = k + 1
        rows = [(r, r.slot) for r in dec]
        # draft catch-up: replay committed-token suffixes the draft pool
        # missed (first spec round in a slot, the bonus position after a
        # fully-accepted round, plain/faulted rounds while speculation
        # cooled down) — KV-only prefill chunks, no host sync
        n_catchup = self.draft.catch_up(tier, pool, rows)
        # ONE key schedule serves the whole round: draft step t consumes
        # keys[t] (token n_generated + t) and verify position j consumes
        # keys[j] — the shared Gumbel draw that makes temperature-row
        # drafts line up with the target's own samples
        keys = np.zeros((s, n, 2), np.uint32)
        temps = np.zeros((n,), np.float32)
        self._key_schedule(dec, s, keys, temps)
        self._dispatch_seq += 1
        with span("serve.draft", self.tracer, self._clock, event="spec_draft",
                  pid=PID_SCHEDULER, tid=self._spec_tid[tier][0], tier=tier,
                  k=k, rows=len(dec), catchup_chunks=n_catchup,
                  dispatch=self._dispatch_seq):
            drafts = self.draft.draft_burst(tier, pool, rows, k, keys[:k],
                                            temps)
            self.n_host_syncs += 1
        window = np.zeros((n, s), np.int32)
        rems = np.zeros((n,), np.int32)
        for r, slot in rows:
            window[slot, 0] = r.last_token
            window[slot, 1:] = drafts[:, slot]
            rems[slot] = r.sampling.max_new_tokens - r.n_generated
        # pin the S-wide verify window per row (planner capped K by each
        # row's budget, so rem >= S and, on a paged pool, nothing lands in
        # the garbage page on the accepted path)
        pool.ensure_decode([slot for _, slot in rows], s,
                           [int(rems[slot]) for _, slot in rows])
        self._dispatch_seq += 1
        verify_dispatch = self._dispatch_seq
        with span("serve.verify", self.tracer, self._clock,
                  event="spec_verify", pid=PID_SCHEDULER,
                  tid=self._spec_tid[tier][1], tier=tier, k=k,
                  rows=len(dec), dispatch=verify_dispatch) as sp:
            try:
                verified = self.engine.verify_slots(pool, window, keys, temps)
            except StepFault as f:
                self._on_fault(dec, f, finished_now)
                return
            self.n_host_syncs += 1
            if self._ft_check and self._tokens_poisoned(
                    verified[:, [slot for _, slot in rows]]):
                # the verify's outputs are dropped whole; target lengths
                # were never committed, so the poisoned KV writes stay
                # masked and the preempt-recompute recovery is
                # bit-identical
                self._on_fault(dec, StepFault("nan",
                                              "verify ids out of vocab"),
                               finished_now)
                return
            # host acceptance: longest agreeing prefix + the target's own
            # bonus/correction sample, truncated by first-EOS and budget
            plan: List[Tuple[Request, int, int]] = []
            drafted = accepted = emitted_total = 0
            for r, slot in rows:
                n_emit, n_acc = accept_longest_prefix(
                    drafts[:, slot], verified[:, slot], r.sampling.eos_id,
                    int(rems[slot]))
                plan.append((r, slot, n_emit))
                drafted += k
                accepted += n_acc
                emitted_total += n_emit
            # commit target lengths FIRST (the verify wrote all S
            # positions; committing only n_emit IS the rejection rollback
            # — everything past the committed length is garbage-but-
            # masked), then sync the draft pool to the committed state in
            # one length assignment
            for r, slot, n_emit in plan:
                pool.lengths[slot] += n_emit
            self.draft.sync_lengths(tier, pool, rows)
            self.spec_planner.observe(drafted, accepted)
            sp.set(accepted=accepted, emitted=emitted_total)
        self.metrics.on_spec_round(
            k=k, rows=len(dec), drafted=drafted, accepted=accepted,
            emitted=emitted_total, catchup_dispatches=n_catchup, tier=tier)
        # step-major emission replay — the exact sequence K+1 single
        # steps would have produced; slots captured pre-emission because
        # _emit may retire a request mid-replay
        with span("serve.emit", tokens=emitted_total) as sp:
            n_done = len(finished_now)
            for t in range(s):
                for r, slot, n_emit in plan:
                    if t < n_emit:
                        self._emit(r, int(verified[t, slot]), emitted,
                                   finished_now, dispatch=verify_dispatch)
            sp.set(finished=len(finished_now) - n_done)

    def run(self, max_steps: Optional[int] = None) -> None:
        """Step until every submitted request is FINISHED."""
        steps = 0
        while self.has_work:
            if max_steps is not None and steps >= max_steps:
                raise RuntimeError(f"scheduler did not drain in {steps} steps")
            self.step()
            steps += 1

    # ------------------------------------------------------------------
    def _emit(self, req: Request, tok: int, emitted: List,
              finished_now: List[Request], dispatch: int = -1) -> None:
        now = self._clock()
        req.output_tokens.append(tok)
        req.token_times.append(now)
        req.token_dispatches.append(dispatch)
        if req.first_token_time is None:
            req.first_token_time = now
        emitted.append((req, req.slot, tok))
        sp = req.sampling
        if sp.eos_id >= 0 and tok == sp.eos_id:
            self._retire(req, "eos", now, finished_now)
        elif req.n_generated >= sp.max_new_tokens:
            self._retire(req, "length", now, finished_now)
        elif req.prompt_len + req.n_generated >= \
                self.pools[req.tier].max_len:
            # defensive: submit() bounds prompt+max_new, so this only fires
            # for requests constructed around the validation.  The device
            # burst mirrors this exact condition in its stop mask.
            self._retire(req, "capacity", now, finished_now)

    def _retire(self, req: Request, reason: str, now: float,
                finished_now: List[Request]) -> None:
        req.state = RequestState.FINISHED
        req.finish_reason = reason
        req.finish_time = now
        del self.running[(req.tier, req.slot)]
        if self.draft is not None:
            self.draft.release(req.tier, req.slot)
        self.pools[req.tier].free(req.slot)
        req.slot = None
        self._retry.clear(req.id)
        self.finished.append(req)
        finished_now.append(req)
        self.metrics.on_finish(req)
        if self.tracer is not None:
            self._trace_request(req)

    def _trace_request(self, req: Request) -> None:
        """Emit the request's lifecycle spans at retirement — the spans
        are reconstructed from the timestamps the hot path already
        stamped, so tracing adds nothing per token."""
        tr = self.tracer
        tid = req.id or 0
        tr.thread_name(PID_REQUESTS, tid, f"req {tid}")
        a, ad = req.arrival_time, req.admit_time
        ft, fin = req.first_token_time, req.finish_time
        if a is not None and ad is not None:
            tr.complete("WAITING", a, ad, pid=PID_REQUESTS, tid=tid,
                        args={"tier": req.tier})
        if ad is not None and ft is not None:
            tr.complete("PREFILL", ad, ft, pid=PID_REQUESTS, tid=tid,
                        args={"prompt_len": req.prompt_len})
        if ft is not None and fin is not None:
            tr.complete("DECODE", ft, fin, pid=PID_REQUESTS, tid=tid,
                        args={"n_generated": req.n_generated})
        if ft is not None:
            tr.instant("first_token", ft, pid=PID_REQUESTS, tid=tid)
        if fin is not None:
            tr.instant("finished", fin, pid=PID_REQUESTS, tid=tid,
                       args={"reason": req.finish_reason,
                             "n_generated": req.n_generated})
