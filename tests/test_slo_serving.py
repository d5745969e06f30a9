"""SLO-aware scheduling tests (DESIGN.md §16): priority preemption with
bit-identical resume, admission control / degradation, deadline
enforcement, and fault-tolerant serving.

THE contract pinned here: scheduling policy changes WHEN tokens are
produced, never WHICH tokens.  A preempted-then-resumed request (and a
fault-recovered one) must emit exactly the unpreempted run's tokens —
greedy and seeded temperature, slab and paged pools, meshless and
dp2 x tp4 — because the resume path recomputes the evicted KV from
prompt + generated[:-1] and decode continues at the preserved
per-(request, step) key schedule.
"""
import json

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.launch.mesh import make_mesh
from repro.models.common import InitMaker, QuantMaker
from repro.models import transformer as T
from repro.serve import (Request, RequestState, SamplingParams, Scheduler,
                         ServeConfig, ServingEngine, SLOPolicy, StepFault)

multi_device = pytest.mark.skipif(
    len(jax.devices()) < 8,
    reason="needs XLA_FLAGS=--xla_force_host_platform_device_count=8")


@pytest.fixture(scope="module")
def setup():
    cfg = get_config("granite-8b", smoke=True)
    params = T.build_params(cfg, InitMaker(jax.random.PRNGKey(0)))
    return cfg, params


def _engine(setup, **kw):
    cfg, params = setup
    args = dict(max_len=48, n_slots=2, prefill_chunk=8)
    args.update(kw)
    return ServingEngine(cfg, params, ServeConfig(**args))


def _prompts(cfg, lens, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, cfg.vocab, (n,)).astype(np.int32) for n in lens]


def _sp(temp=0.0, max_new=24):
    return SamplingParams(max_new_tokens=max_new, temperature=temp, seed=7)


def _outputs(sched):
    return {r.id: list(r.output_tokens) for r in sched.finished}


def _contended_run(engine, prompts, temp):
    """Two low-priority requests fill both slots and reach DECODE; a
    high-priority arrival then forces a preemption."""
    s = Scheduler(engine)
    s.submit(Request(prompts[1], _sp(temp), id=1, priority=5))
    s.submit(Request(prompts[2], _sp(temp), id=2, priority=5))
    for _ in range(5):
        s.step()
    s.submit(Request(prompts[0], _sp(temp), id=0, priority=0))
    s.run(max_steps=500)
    return s


# ---------------------------------------------------------------------------
# Preempt-and-resume bit-identity (the tentpole contract)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("paged", [False, True], ids=["slab", "paged"])
@pytest.mark.parametrize("temp", [0.0, 0.8], ids=["greedy", "temp"])
def test_preempt_resume_bit_identical(setup, paged, temp):
    cfg, _ = setup
    engine = _engine(setup, paged=paged)
    prompts = _prompts(cfg, [16, 12, 9])

    base = Scheduler(engine)
    for i, p in enumerate(prompts):
        base.submit(Request(p, _sp(temp), id=i))
    base.run(max_steps=500)
    ref = _outputs(base)
    assert all(len(v) == 24 for v in ref.values())

    s = _contended_run(engine, prompts, temp)
    assert sum(r.n_preemptions for r in s.finished) >= 1, \
        "scenario must actually preempt"
    assert _outputs(s) == ref
    rep = s.metrics.report()
    assert rep["finish_reasons"]["preempted_resumed"] >= 1
    assert rep["preempt_reasons"] == {"priority": rep["preemptions"]}
    if paged:
        # the resume re-admission adopted the victim's registered prompt
        # pages from the prefix cache instead of re-prefilling them
        assert rep["prefix_hits"] >= 1 and rep["prefix_hit_tokens"] >= 8


def test_preempted_request_keeps_id_and_output(setup):
    """The resume preserves identity: same request object, same id, the
    pre-preemption tokens never re-emitted (n_generated monotone)."""
    cfg, _ = setup
    engine = _engine(setup)
    prompts = _prompts(cfg, [16, 12, 9])
    s = _contended_run(engine, prompts, 0.0)
    victim = next(r for r in s.finished if r.n_preemptions > 0)
    assert victim.finish_reason == "length"
    assert len(victim.output_tokens) == 24
    assert victim.resume_prompt is None          # consumed by the replay
    assert victim.slot is None


def test_victim_selection_lowest_class_least_generated(setup):
    """Among DECODE slots, the victim is the lowest class; ties break to
    the least-generated (cheapest recompute).  Equal-class waiters never
    preempt (no livelock by slot trading)."""
    cfg, _ = setup
    engine = _engine(setup, n_slots=3)
    prompts = _prompts(cfg, [8, 8, 8, 8])
    s = Scheduler(engine, max_burst=1)     # step-granular n_generated
    s.submit(Request(prompts[0], _sp(max_new=24), id=0, priority=1))
    s.step(); s.step()                      # id 0 decodes first (oldest)
    s.submit(Request(prompts[1], _sp(max_new=24), id=1, priority=5))
    s.submit(Request(prompts[2], _sp(max_new=24), id=2, priority=5))
    for _ in range(6):
        s.step()
    gen = {r.id: r.n_generated for r in s.running.values()}
    assert set(gen) == {0, 1, 2}
    # equal-class arrival: nobody preempted
    s.submit(Request(prompts[3], _sp(max_new=4), id=3, priority=5))
    s.step()
    assert all(r.n_preemptions == 0 for r in s.running.values())
    assert len(s.waiting) == 1
    # higher-class arrival: evicts from class 5 (never the class-1 slot),
    # picking the least-generated of the two
    s.submit(Request(prompts[3], _sp(max_new=4), id=4, priority=0))
    s.step()
    preempted = [r for r in s.waiting if r.n_preemptions > 0]
    assert [r.id for r in preempted] == [2]     # class 5, least generated
    assert gen[2] <= gen[1]
    s.run(max_steps=500)
    assert all(r.finish_reason == "length" for r in s.finished)


@multi_device
def test_preempt_resume_bit_identical_dp2_tp4(setup):
    """The tentpole contract under the mesh: preempt-and-resume on a
    dp=2 x tp=4 mesh, quantized weights and int8 KV, emits exactly the
    unpreempted meshless tokens at the same kernel mode."""
    from repro.quant.policy import PrecisionPolicy
    cfg = get_config("granite-8b", smoke=True)
    params = T.build_params(cfg, QuantMaker(jax.random.PRNGKey(0)))
    prompts = _prompts(cfg, [16, 12, 9], seed=5)

    def engine(mesh, kernel="auto"):
        return ServingEngine(cfg, params, ServeConfig(
            max_len=48, n_slots=2, prefill_chunk=8,
            policy=PrecisionPolicy(kv="int8", kernel=kernel), mesh=mesh))

    base = Scheduler(engine(None, "pallas"))
    for i, p in enumerate(prompts):
        base.submit(Request(p, _sp(0.0), id=i))
    base.run(max_steps=500)
    ref = _outputs(base)

    mesh = make_mesh((2, 4), ("data", "model"))
    s = _contended_run(engine(mesh), prompts, 0.0)
    assert sum(r.n_preemptions for r in s.finished) >= 1
    assert _outputs(s) == ref
    assert s.metrics.report()["topology"] == \
        {"n_devices": 8, "dp": 2, "tp": 4}


# ---------------------------------------------------------------------------
# Deadlines (virtual clock; step-granular enforcement)
# ---------------------------------------------------------------------------
def test_ttft_deadline_sheds_waiting(setup):
    cfg, _ = setup
    t = [0.0]
    engine = _engine(setup, n_slots=1)
    s = Scheduler(engine, clock=lambda: t[0])
    s.submit(Request(_prompts(cfg, [8])[0], _sp(max_new=16), id=0))
    # Seat id 0 in the single slot before the deadline request arrives:
    # EDF admission would otherwise run the deadline-carrying request
    # first (finite key beats inf), and it would meet its deadline.
    s.step()
    s.submit(Request(_prompts(cfg, [8], 1)[0], _sp(max_new=4), id=1,
                     ttft_deadline_s=0.5))
    for _ in range(3):
        s.step()
        t[0] += 1.0
    s.run(max_steps=500)
    reasons = {r.id: r.finish_reason for r in s.finished}
    assert reasons == {0: "length", 1: "deadline_exceeded"}
    rep = s.metrics.report()
    assert rep["finish_reasons"]["deadline_exceeded"] == 1
    # the shed request pollutes no latency percentile
    assert len(s.metrics.ttft) == 1 and len(s.metrics.e2e) == 1


def test_e2e_deadline_retires_running(setup):
    cfg, _ = setup
    t = [0.0]
    engine = _engine(setup, n_slots=1)
    s = Scheduler(engine, clock=lambda: t[0])
    r = s.submit(Request(_prompts(cfg, [8])[0], _sp(max_new=32), id=0,
                         e2e_deadline_s=2.5))
    while not r.is_finished:
        s.step()
        t[0] += 1.0
    assert r.finish_reason == "deadline_exceeded"
    assert 0 < r.n_generated < 32                # partial output delivered
    assert r.slot is None                        # slot returned to the pool
    assert s.pool.n_free == s.pool.n_slots


# ---------------------------------------------------------------------------
# Admission control + graceful degradation (serve.slo)
# ---------------------------------------------------------------------------
def test_rejection_typed_and_protect_priority(setup):
    cfg, _ = setup
    engine = _engine(setup)
    s = Scheduler(engine, slo=SLOPolicy(max_waiting=2, protect_priority=0))
    rejected = 0
    for p in _prompts(cfg, [8] * 8):
        r = s.submit(Request(p, _sp(max_new=4), priority=2))
        if r.is_finished:
            rejected += 1
            assert r.finish_reason == "rejected"
            assert r.rejection.kind == "queue_full"
            assert r.rejection.to_dict()["kind"] == "queue_full"
    assert rejected > 0
    protected = s.submit(Request(_prompts(cfg, [8], 9)[0], _sp(max_new=4),
                                 priority=0))
    assert not protected.is_finished, "protected class is never rejected"
    s.run(max_steps=500)
    rep = s.metrics.report()
    assert rep["rejection_kinds"] == {"queue_full": rejected}
    assert rep["finish_reasons"]["rejected"] == rejected


def test_drain_time_and_deadline_unmeetable_rejections(setup):
    cfg, _ = setup
    engine = _engine(setup)
    s = Scheduler(engine, slo=SLOPolicy(max_queue_delay_s=1e-9))
    first = s.submit(Request(_prompts(cfg, [8])[0], _sp(max_new=4),
                             priority=1))
    assert not first.is_finished             # empty system: est 0 accepted
    second = s.submit(Request(_prompts(cfg, [8], 1)[0], _sp(max_new=4),
                              priority=1))
    assert second.is_finished and second.rejection.kind == "drain_time"
    assert second.rejection.estimate_s > 0
    s.run(max_steps=500)

    s2 = Scheduler(engine, slo=SLOPolicy())
    s2.submit(Request(_prompts(cfg, [8])[0], _sp(max_new=4), priority=1))
    doomed = s2.submit(Request(_prompts(cfg, [8], 1)[0], _sp(max_new=4),
                               priority=1, ttft_deadline_s=1e-12))
    assert doomed.is_finished
    assert doomed.rejection.kind == "deadline_unmeetable"
    s2.run(max_steps=500)


def test_downgrade_hysteresis_engage_hold_release(setup):
    cfg, _ = setup
    engine = _engine(setup)
    hi = 1e-6
    slo = SLOPolicy(downgrade_map={"bf16": "int8"},
                    downgrade_high_s=hi, downgrade_low_s=hi / 10)
    s = Scheduler(engine, tiers=["bf16", "int8"], slo=slo)
    r1 = s.submit(Request(_prompts(cfg, [8])[0], _sp(max_new=8)))
    assert r1.tier == "bf16" and not slo.degraded
    r2 = s.submit(Request(_prompts(cfg, [8], 1)[0], _sp(max_new=8)))
    assert slo.degraded and r2.tier == "int8" and r2.downgraded_from == "bf16"
    # hold: a request already downgraded is never re-downgraded, and the
    # flag holds while the estimate sits inside the band
    assert slo.downgrade_low_s < slo.last_estimate_s
    s.run(max_steps=500)
    r3 = s.submit(Request(_prompts(cfg, [8], 2)[0], _sp(max_new=8)))
    assert not slo.degraded and r3.tier == "bf16" \
        and r3.downgraded_from is None           # released below low water
    s.run(max_steps=500)
    assert s.metrics.report()["downgrades"] == 1
    # downgraded request still finished at the denser tier
    assert next(r for r in s.finished if r is r2).tier == "int8"


def test_slo_policy_validation():
    with pytest.raises(ValueError, match="both"):
        SLOPolicy(downgrade_high_s=1.0)
    with pytest.raises(ValueError, match="inverted"):
        SLOPolicy(downgrade_high_s=1.0, downgrade_low_s=2.0)
    with pytest.raises(ValueError, match="never fires"):
        SLOPolicy(downgrade_map={"bf16": "int8"})


def test_cost_model_planning(setup):
    """burst_cap / prefill_chunks_per_step size work from the analytical
    model: tiny budgets clamp to 1, generous budgets open up, and the
    scheduler's burst plan under a tiny budget stays K=1 end to end."""
    cfg, _ = setup
    engine = _engine(setup, n_slots=4)
    tight = SLOPolicy(max_step_s=1e-12)
    loose = SLOPolicy(max_step_s=10.0)
    s = Scheduler(engine, slo=tight)
    for i, p in enumerate(_prompts(cfg, [8, 8])):
        s.submit(Request(p, _sp(max_new=8), id=i))
    s.run(max_steps=500)
    rep = s.metrics.report()
    assert set(rep["burst_hist"]) == {"1"}       # cost cap forces K=1
    assert tight.prefill_chunks_per_step(s) == 1
    s2 = Scheduler(engine, slo=loose)
    for i, p in enumerate(_prompts(cfg, [8, 8])):
        s2.submit(Request(p, _sp(max_new=8), id=i))
    dec = list(s2.running.values())
    assert loose.burst_cap(s2, dec, s2.pool, 8) == 8
    assert loose.prefill_chunks_per_step(s2) >= 1
    s2.run(max_steps=500)
    assert tight.estimate_queue_delay_s(s2) == 0.0   # drained system


# ---------------------------------------------------------------------------
# Fault tolerance on the hot path
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("paged", [False, True], ids=["slab", "paged"])
@pytest.mark.parametrize("mode", ["injected", "nan"])
def test_fault_recovery_bit_identical(setup, paged, mode):
    """One killed (or NaN-poisoned) decode dispatch: the cohort requeues
    through preempt-and-resume and the final outputs are bit-identical to
    the fault-free run."""
    cfg, _ = setup
    prompts = _prompts(cfg, [16, 12])

    def run(injector, paged_):
        eng = _engine(setup, paged=paged_, fault_injector=injector)
        s = Scheduler(eng)
        for i, p in enumerate(prompts):
            s.submit(Request(p, _sp(temp=0.8, max_new=12), id=i))
        s.run(max_steps=2000)
        return s

    ref = _outputs(run(None, paged))
    fired = []
    s = run(lambda kind, seq: (fired.append(seq) or mode)
            if seq == 5 and not fired else None, paged)
    assert fired == [5]
    assert _outputs(s) == ref
    rep = s.metrics.report()
    assert rep["faults"] == 1 and rep["fault_kinds"] == {mode: 1}
    assert rep["preempt_reasons"].get("fault", 0) >= 1


def test_fault_exhaustion_and_backoff(setup):
    """Every decode dispatch dies: each request burns max_fault_retries+1
    faults with exponentially-spaced holds, then retires with
    finish_reason='fault'; slots and pages all return to the pool."""
    cfg, _ = setup
    eng = _engine(setup, paged=True, max_fault_retries=2,
                  fault_injector=lambda kind, seq:
                  "injected" if kind != "prefill" else None)
    s = Scheduler(eng)
    reqs = [s.submit(Request(p, _sp(max_new=8), id=i))
            for i, p in enumerate(_prompts(cfg, [16, 12]))]
    holds = {}
    while s.has_work:
        s.step()
        for r in s.waiting:
            if r.n_faults:
                holds.setdefault(r.id, []).append(
                    r.hold_until_step - s.n_steps)
        assert s.n_steps < 2000
    for r in reqs:
        assert r.finish_reason == "fault"
        assert r.n_faults == 3                   # budget 2 -> 3rd exhausts
        assert r.slot is None
    # exponential backoff really spaced the retries: the second fault's
    # 2-step hold is still pending a full step after it was charged
    # (holds are sampled post-increment, so a value of k means k more
    # rounds before the request is eligible again)
    assert any(max(h) >= 1 for h in holds.values())
    from repro.serve import RetryBudget
    rb = RetryBudget(max_retries=3)
    assert [rb.record_fault("x") for _ in range(4)] == [1, 2, 4, None]
    rb.clear("x")
    assert rb.n_faults("x") == 0
    assert s.pool.n_free == s.pool.n_slots
    assert s.pool.check() if hasattr(s.pool, "check") else True
    rep = s.metrics.report()
    assert rep["finish_reasons"]["fault"] == 2
    assert rep["fault_requests"] >= 6


def test_injector_none_is_inert(setup):
    """No injector: the fault machinery adds nothing — outputs match a
    plain engine's and the poisoned-token guard never arms."""
    cfg, _ = setup
    prompts = _prompts(cfg, [16, 12])
    plain = Scheduler(_engine(setup))
    hooked = Scheduler(_engine(setup, fault_injector=None))
    for s in (plain, hooked):
        for i, p in enumerate(prompts):
            s.submit(Request(p, _sp(max_new=8), id=i))
        s.run(max_steps=500)
    assert _outputs(plain) == _outputs(hooked)
    assert not hooked._ft_check
    assert hooked.metrics.n_fault_events == 0


# ---------------------------------------------------------------------------
# Metrics accounting
# ---------------------------------------------------------------------------
def test_accounting_identity_and_json_clean(setup):
    """Every submitted request lands in exactly one disjoint finish
    reason; the report round-trips RFC JSON; per-priority percentiles
    appear when more than one class was served."""
    cfg, _ = setup
    t = [0.0]
    engine = _engine(setup)
    s = Scheduler(engine, clock=lambda: t[0],
                  slo=SLOPolicy(max_waiting=3, protect_priority=0))
    rng = np.random.default_rng(3)
    for i in range(12):
        s.submit(Request(
            rng.integers(1, cfg.vocab, (8,)).astype(np.int32),
            _sp(max_new=4), priority=int(i % 2) * 5,
            ttft_deadline_s=4.0 if i % 3 == 0 else None))
        t[0] += 0.1
    while s.has_work:
        s.step()
        t[0] += 1.0
        assert s.n_steps < 500
    rep = s.metrics.report()
    assert json.loads(json.dumps(rep, allow_nan=False)) == rep
    disjoint = sum(v for k, v in rep["finish_reasons"].items()
                   if k != "preempted_resumed")
    assert disjoint == rep["n_requests"] == s.metrics.n_arrived == 12
    assert set(rep["queue_wait_p50_s"]) <= {"0", "5"}
    assert "per_priority" in rep
    for cls in rep["per_priority"].values():
        for k in cls:
            assert cls[k] is not None
