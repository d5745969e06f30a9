"""SLO policy: admission control, graceful degradation, cost-model planning.

The scheduler (PRs 1-8) is work-conserving but policy-free: at sustained
overload every request's TTFT grows without bound, because nothing ever
says no.  ``SLOPolicy`` is the piece that says no — three levers, all
driven by the analytical cost model (``perfmodel.analytical.decode_latency``,
the paper's two-phase streaming model):

  * **Admission control** — ``admit()`` estimates the queue's drain time
    and sheds work with a typed ``Rejection`` (queue_full / drain_time /
    deadline_unmeetable) instead of letting it rot in the queue.
    Requests at or above ``protect_priority`` (class numbers <= it) are
    never rejected — overload sheds best-effort traffic so the protected
    classes' TTFT stays bounded (the scheduler's preemption handles the
    slots those classes need).
  * **Graceful degradation** — under pressure, ``admit()`` downgrades
    ``Request.kv_policy`` along ``downgrade_map`` (e.g. bf16 -> int8):
    per-request KV tiers (DESIGN.md §12) make precision a *runtime*
    capacity lever, which is exactly the XtraMAC/MixPE/FlexiBit
    mixed-precision-as-mechanism thesis lifted to the scheduler.  The
    downgrade engages above ``downgrade_high_s`` estimated drain and
    disengages below ``downgrade_low_s`` — hysteresis, so a workload
    sitting at the threshold doesn't flap between tiers.
  * **Cost-model planning** — ``burst_cap()`` and
    ``prefill_chunks_per_step()`` size the decode burst K and the
    prefill share of each round from modeled step latency against
    ``max_step_s``, instead of the fixed ``max_burst`` / one-chunk caps.

All time thresholds are in COST-MODEL seconds (the analytical FPGA
pricing), not host wall seconds — on a CPU smoke host the two differ by
orders of magnitude, but the model is monotone in backlog, which is what
admission control needs: thresholds calibrate once per deployment.
Estimates are pure functions of scheduler state; the policy adds no
clock calls and no host syncs (DESIGN.md §16).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional

from .request import RequestState


@dataclasses.dataclass(frozen=True)
class Rejection:
    """Typed admission verdict for a shed request (``Request.rejection``).

    ``kind``: 'queue_full' (waiting depth cap), 'drain_time' (estimated
    queue drain beyond the policy cap), or 'deadline_unmeetable' (the
    request's own TTFT deadline is provably beyond the estimated drain).
    ``estimate_s`` is the cost-model drain estimate the verdict was based
    on, for post-hoc audit in bench reports."""
    kind: str
    detail: str
    estimate_s: Optional[float] = None

    def to_dict(self) -> Dict:
        return {"kind": self.kind, "detail": self.detail,
                "estimate_s": self.estimate_s}


class SLOPolicy:
    def __init__(self, *,
                 max_queue_delay_s: Optional[float] = None,
                 max_waiting: Optional[int] = None,
                 protect_priority: int = 0,
                 downgrade_map: Optional[Dict[str, str]] = None,
                 downgrade_high_s: Optional[float] = None,
                 downgrade_low_s: Optional[float] = None,
                 max_step_s: Optional[float] = None,
                 design: str = "xtramac",
                 scheme: Optional[str] = None):
        """``max_queue_delay_s``: reject unprotected arrivals once the
        estimated drain exceeds this (None = never reject on drain).
        ``max_waiting``: hard waiting-queue depth cap for unprotected
        arrivals.  ``protect_priority``: requests with
        ``priority <= protect_priority`` are never rejected.
        ``downgrade_map``: {from_tier: to_tier} applied while degraded;
        degradation engages at ``downgrade_high_s`` estimated drain and
        releases at ``downgrade_low_s`` (must be < high — the hysteresis
        band).  ``max_step_s``: modeled per-round latency budget that
        sizes decode bursts and prefill chunks per step (None = keep the
        scheduler's fixed caps).  ``design`` / ``scheme`` pick the
        analytical deployment priced (``perfmodel.analytical``)."""
        if (downgrade_high_s is None) != (downgrade_low_s is None):
            raise ValueError("give both downgrade_high_s and "
                             "downgrade_low_s, or neither")
        if downgrade_high_s is not None \
                and not downgrade_low_s < downgrade_high_s:
            raise ValueError(
                f"hysteresis band inverted: downgrade_low_s "
                f"{downgrade_low_s} must be < downgrade_high_s "
                f"{downgrade_high_s}")
        if downgrade_map and downgrade_high_s is None:
            raise ValueError("downgrade_map without downgrade_high_s/"
                             "downgrade_low_s thresholds never fires")
        self.max_queue_delay_s = max_queue_delay_s
        self.max_waiting = max_waiting
        self.protect_priority = protect_priority
        self.downgrade_map = dict(downgrade_map or {})
        self.downgrade_high_s = downgrade_high_s
        self.downgrade_low_s = downgrade_low_s
        self.max_step_s = max_step_s
        self.design = design
        self.scheme = scheme
        self.degraded = False           # hysteresis state
        self.last_estimate_s: Optional[float] = None
        self._step_memo: Dict = {}

    # ------------------------------------------------------------------
    # Cost model: one decode token-step at a given shape (memoized; the
    # context is bucketed to a power of two so the memo stays small)
    # ------------------------------------------------------------------
    def _model_step_s(self, engine, batch: int, context: int,
                      kv_bytes_per_token: int) -> float:
        batch = max(int(batch), 1)
        context = max(int(context), 1)
        ctx_bucket = 1 << (context - 1).bit_length()
        key = (batch, ctx_bucket, kv_bytes_per_token)
        t = self._step_memo.get(key)
        if t is None:
            from repro.perfmodel.analytical import (_DEPLOY, decode_latency,
                                                    gemv_engine_for)
            want = self.scheme or engine.cfg.scheme_proj or "w8a8"
            scheme = want if want in _DEPLOY else "w8a8"
            t = decode_latency(
                engine.cfg, scheme, batch=batch, context=ctx_bucket,
                design=self.design,
                kv_bytes_per_token=kv_bytes_per_token,
                engine_model=gemv_engine_for(scheme))["t_total_s"]
            self._step_memo[key] = t
        return t

    def estimate_queue_delay_s(self, sched) -> float:
        """Cost-model estimate of the time to drain everything currently
        in the system: outstanding decode tokens amortize over the total
        slot width; outstanding prefill tokens serialize one chunk per
        round on top (the scheduler's interleaving policy).  Monotone in
        backlog — the property admission control keys on.

        With speculative decoding active (DESIGN.md §17) the decode
        backlog drains in draft/verify rounds instead of single steps:
        each round costs K draft steps at the draft tier's KV bytes plus
        one verify priced as a plain target step — the optimistic bound
        where the K+1-wide verify compute rides the same weight/KV
        stream (idle-headroom regime; see spec_round_latency) — and
        delivers E = (1 - a^(K+1)) / (1 - a) tokens per row at the
        controller's acceptance EMA — so admission prices speculative
        throughput instead of assuming one token per dispatch."""
        engine = sched.engine
        pool = sched.pool
        n_slots = sum(p.n_slots for p in sched.pools.values())
        dec_toks = 0
        pre_toks = 0
        ctx_sum, ctx_n = 0, 0
        for r in sched.running.values():
            dec_toks += max(r.sampling.max_new_tokens - r.n_generated, 0)
            if r.state is RequestState.PREFILL:
                pre_toks += max(r.prefill_len - r.prefill_pos, 0)
            if r.slot is not None:
                ctx_sum += int(sched.pools[r.tier].lengths[r.slot])
                ctx_n += 1
        for r in sched.waiting:
            dec_toks += r.sampling.max_new_tokens
            pre_toks += r.prefill_len
        context = ctx_sum // ctx_n if ctx_n else pool.max_len // 2
        t_tok = self._model_step_s(engine, n_slots, context,
                                   pool.bytes_per_token)
        C = engine.scfg.prefill_chunk
        planner = getattr(sched, "spec_planner", None)
        if planner is not None and planner.active:
            draft = getattr(sched, "draft", None)
            dpool = draft.pools.get(sched.default_tier) \
                if draft is not None else None
            draft_bpt = dpool.bytes_per_token if dpool is not None \
                else pool.bytes_per_token
            t_draft = self._model_step_s(engine, n_slots, context,
                                         draft_bpt)
            t_round = planner.k * t_draft + t_tok
            e_tokens = max(planner.expected_tokens_per_round(), 1.0)
            est = (dec_toks / max(n_slots, 1)) / e_tokens * t_round \
                + (pre_toks / C) * t_tok
        else:
            rounds = dec_toks / max(n_slots, 1) + pre_toks / C
            est = rounds * t_tok
        self.last_estimate_s = est
        return est

    # ------------------------------------------------------------------
    # Admission (called by Scheduler.submit)
    # ------------------------------------------------------------------
    def admit(self, req, sched) -> Optional[Rejection]:
        """Admission verdict for ``req`` against the scheduler's current
        backlog.  Returns None to accept (possibly after downgrading the
        request's KV tier in place — the scheduler re-resolves the tier
        and records the downgrade), or a typed ``Rejection`` to shed."""
        est = self.estimate_queue_delay_s(sched)
        # hysteresis: engage above high, release below low, hold between
        if self.downgrade_high_s is not None:
            if not self.degraded and est > self.downgrade_high_s:
                self.degraded = True
            elif self.degraded and est < self.downgrade_low_s:
                self.degraded = False
        if self.degraded and self.downgrade_map:
            cur = req.kv_policy if req.kv_policy is not None \
                else sched.default_tier
            target = self.downgrade_map.get(cur)
            if target is not None and target in sched.pools \
                    and req.downgraded_from is None:
                req.downgraded_from = cur
                req.kv_policy = target
        if req.priority <= self.protect_priority:
            return None
        if self.max_waiting is not None \
                and len(sched.waiting) >= self.max_waiting:
            return Rejection(
                "queue_full",
                f"{len(sched.waiting)} waiting >= cap {self.max_waiting}",
                est)
        if self.max_queue_delay_s is not None \
                and est > self.max_queue_delay_s:
            return Rejection(
                "drain_time",
                f"estimated drain {est:.3g}s > cap "
                f"{self.max_queue_delay_s:.3g}s", est)
        if req.ttft_deadline_s is not None and est > req.ttft_deadline_s:
            return Rejection(
                "deadline_unmeetable",
                f"estimated drain {est:.3g}s > ttft deadline "
                f"{req.ttft_deadline_s:.3g}s", est)
        return None

    # ------------------------------------------------------------------
    # Cost-model planning (called by Scheduler per round)
    # ------------------------------------------------------------------
    def burst_cap(self, sched, cohort: List, pool, max_burst: int) -> int:
        """Largest decode-burst K whose modeled wall fits ``max_step_s``
        (the scheduler still applies its own event-horizon and power-of-
        two policies on top, so the cap only ever shrinks a burst)."""
        if self.max_step_s is None or not cohort:
            return max_burst
        ctx = sum(int(pool.lengths[r.slot]) for r in cohort) // len(cohort)
        t = self._model_step_s(sched.engine, len(cohort), ctx,
                               pool.bytes_per_token)
        if t <= 0:
            return max_burst
        return max(1, min(max_burst, int(self.max_step_s / t)))

    def prefill_chunks_per_step(self, sched) -> int:
        """How many prefill-chunk dispatches one scheduling round may
        issue: enough to fill ``max_step_s`` of modeled latency (a chunk
        of C tokens is priced as C single-row token-steps — the model
        covers decode; prefill reuses it as a proxy), at least 1, capped
        at 8 so a pathological budget cannot starve decode."""
        if self.max_step_s is None:
            return 1
        engine = sched.engine
        pool = sched.pool
        C = engine.scfg.prefill_chunk
        t_chunk = C * self._model_step_s(engine, 1, pool.max_len // 2,
                                         pool.bytes_per_token)
        if t_chunk <= 0:
            return 1
        return max(1, min(8, int(self.max_step_s / t_chunk)))

    def snapshot(self) -> Dict:
        """Policy state for reports (bench / obs)."""
        return {
            "degraded": self.degraded,
            "last_estimate_s": self.last_estimate_s,
            "max_queue_delay_s": self.max_queue_delay_s,
            "max_waiting": self.max_waiting,
            "protect_priority": self.protect_priority,
            "downgrade_map": dict(self.downgrade_map),
            "downgrade_high_s": self.downgrade_high_s,
            "downgrade_low_s": self.downgrade_low_s,
            "max_step_s": self.max_step_s,
        }
