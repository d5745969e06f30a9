"""Speculative-decoding tests (DESIGN.md §17).

The contract under test: a draft/verify round — K draft tokens under the
aggressive low-precision draft engine, one (K+1)-position target verify,
longest-agreeing-prefix acceptance — emits tokens BIT-IDENTICAL to the
non-speculative scheduler, greedy AND seeded temperature, slab AND paged
pools, single-device AND dp x tp.  Correctness never depends on the
draft: the adversarial corrupt-drafts harness (0 acceptance) must still
produce identical output AND identical committed KV bytes (the
length-only rollback invariant), and the K-controller must fall back to
plain bursts with bounded O(1) probe cost when acceptance collapses.
EDF admission ordering and the spec accounting identities ride along.
"""
import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.launch.mesh import make_mesh
from repro.models.common import InitMaker, QuantMaker
from repro.models import transformer as T
from repro.serve import (Request, SamplingParams, ServeConfig,
                         ServingEngine, Scheduler, SpecConfig, SpecPlanner)
from repro.serve.spec import DraftEngine, accept_longest_prefix

multi_device = pytest.mark.skipif(
    len(jax.devices()) < 8,
    reason="needs XLA_FLAGS=--xla_force_host_platform_device_count=8")


@pytest.fixture(scope="module")
def setup():
    cfg = get_config("granite-8b", smoke=True)
    params = T.build_params(cfg, InitMaker(jax.random.PRNGKey(0)))
    return cfg, params


def make_engine(setup, *, paged=False, mesh=None, max_len=48, n_slots=4):
    cfg, params = setup
    return ServingEngine(cfg, params, ServeConfig(
        max_len=max_len, n_slots=n_slots, prefill_chunk=8, max_burst=8,
        paged=paged, mesh=mesh))


@pytest.fixture(scope="module")
def engine(setup):
    return make_engine(setup)


@pytest.fixture(scope="module")
def paged_engine(setup):
    return make_engine(setup, paged=True)


def _prompts(engine, n, lens, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, engine.cfg.vocab, (lens[i % len(lens)],))
            .astype(np.int32) for i in range(n)]


def _run(engine, prompts, *, spec=None, max_new=9, temperature=0.0,
         seed=0, max_burst=8, deadlines=None, priorities=None):
    sched = Scheduler(engine, max_burst=max_burst, spec=spec)
    sp = SamplingParams(temperature=temperature, max_new_tokens=max_new,
                        seed=seed)
    reqs = [sched.submit(Request(
        prompt=p, sampling=sp,
        ttft_deadline_s=deadlines[i] if deadlines else None,
        priority=priorities[i] if priorities else 0))
        for i, p in enumerate(prompts)]
    sched.run(max_steps=600)
    assert all(r.is_finished for r in reqs)
    return [list(r.output_tokens) for r in reqs], sched


def _spec_ran(sched):
    m = sched.metrics
    assert m.spec_rounds > 0, "no speculative round ever dispatched"
    assert m.spec_tokens_accepted > 0, "speculation accepted nothing"


# ---------------------------------------------------------------------------
# THE contract: spec-on == spec-off, bit for bit
# ---------------------------------------------------------------------------
def test_spec_bit_identical_greedy_slab(engine):
    """Greedy, slab pool: accepted output equals the non-speculative run
    request for request, while verify dispatches each deliver > 1 token
    (the accepted prefix + bonus) — the whole point of drafting."""
    prompts = _prompts(engine, 3, [9, 6, 11], seed=1)
    ref, _ = _run(engine, prompts, max_new=17)
    got, s = _run(engine, prompts, max_new=17, spec=SpecConfig())
    assert got == ref
    _spec_ran(s)
    rep = s.metrics.report()["spec"]
    assert rep["emitted_per_verify_dispatch"] > 1.0


def test_spec_bit_identical_seeded_temperature_slab(engine):
    """Seeded temperature: the draft samples with each request's REAL
    per-(id, n_generated) key schedule and the verify re-samples every
    window position with the same keys, so even stochastic continuations
    are bit-identical — and a different seed still changes them."""
    prompts = _prompts(engine, 3, [8, 11, 6], seed=2)
    ref, _ = _run(engine, prompts, max_new=17, temperature=0.8, seed=13)
    got, s = _run(engine, prompts, max_new=17, temperature=0.8, seed=13,
                  spec=SpecConfig())
    assert got == ref
    _spec_ran(s)
    other, _ = _run(engine, prompts, max_new=17, temperature=0.8, seed=14,
                    spec=SpecConfig())
    assert other != ref


def test_spec_bit_identical_paged(paged_engine):
    """Paged pool: the verify window is pinned via ensure_decode(K+1) and
    rollback is the same length-only commit, so page indirection changes
    nothing — greedy and temperature."""
    prompts = _prompts(paged_engine, 3, [9, 6, 8], seed=3)
    for temp, seed in ((0.0, 0), (0.8, 13)):
        ref, _ = _run(paged_engine, prompts, max_new=17,
                      temperature=temp, seed=seed)
        got, s = _run(paged_engine, prompts, max_new=17, temperature=temp,
                      seed=seed, spec=SpecConfig())
        assert got == ref, f"temp={temp}"
        _spec_ran(s)


def test_spec_bit_identical_mesh_1x1(setup, engine):
    """A (1, 1) mesh walks the sharded verify/draft path (explicit cache
    shardings, donation) — fast-loop coverage of the §10 plumbing."""
    prompts = _prompts(engine, 2, [9, 6], seed=4)
    ref, _ = _run(engine, prompts, max_new=13)
    mesh = make_mesh((1, 1), ("data", "model"))
    meng = make_engine(setup, mesh=mesh)
    got, s = _run(meng, prompts, max_new=13, spec=SpecConfig())
    assert got == ref
    _spec_ran(s)


def test_spec_eos_retires_at_identical_position(engine):
    """An EOS inside the accepted window truncates emission exactly where
    the plain scheduler would retire — acceptance never emits past EOS."""
    prompts = _prompts(engine, 1, [8], seed=5)
    probe, _ = _run(engine, prompts, max_new=12)
    seq = probe[0]
    i = next(j for j in range(1, len(seq)) if seq[j] not in seq[:j])
    eos = int(seq[i])
    sp = SamplingParams(max_new_tokens=16, eos_id=eos)

    def run(spec):
        sched = Scheduler(engine, spec=spec)
        req = sched.submit(Request(prompt=prompts[0], sampling=sp))
        sched.run(max_steps=200)
        return req, sched

    r_ref, _ = run(None)
    r_spec, s = run(SpecConfig())
    assert r_spec.output_tokens == r_ref.output_tokens
    assert r_spec.finish_reason == r_ref.finish_reason == "eos"
    assert r_spec.n_generated == i + 1
    _spec_ran(s)
    # slot returned despite the mid-window retire
    assert s.pool.n_free == s.pool.n_slots


@multi_device
def test_spec_dp2_tp4_bit_identical():
    """Speculation under the dp=2 x tp=4 mesh (8 forced host devices),
    quantized weights + int8 target KV, greedy and temperature sampling:
    identical to the non-speculative run AT THE SAME GEOMETRY.  The
    reference is the meshed plain scheduler — the spec contract is
    "speculation changes nothing", while meshed-vs-meshless numerics
    is test_sharded_serving.py's contract, pinned separately."""
    cfg = get_config("granite-8b", smoke=True)
    params = T.build_params(cfg, QuantMaker(jax.random.PRNGKey(0)))
    rng = np.random.default_rng(11)
    prompts = [rng.integers(1, cfg.vocab, (n,)).astype(np.int32)
               for n in (9, 6, 11)]

    def eng():
        return ServingEngine(cfg, params, ServeConfig(
            max_len=32, n_slots=8, prefill_chunk=8, kv_dtype="int8",
            max_burst=8, mesh=make_mesh((2, 4), ("data", "model"))))

    for temp, seed in ((0.0, 0), (0.7, 5)):
        ref, _ = _run(eng(), prompts, max_new=13,
                      temperature=temp, seed=seed)
        got, s = _run(eng(), prompts, max_new=13, temperature=temp,
                      seed=seed, spec=SpecConfig())
        assert got == ref, f"temp={temp}"
        _spec_ran(s)


# ---------------------------------------------------------------------------
# Rejection rollback: corrupted drafts, byte-equal committed KV
# ---------------------------------------------------------------------------
def _committed_kv(pool, slot, length):
    """Every cache leaf's committed prefix for ``slot`` (leaves are
    stacked [layer, slot, pos, ...]; positions >= length are
    garbage-but-masked and excluded by contract)."""
    return [np.asarray(leaf)[:, slot, :length]
            for leaf in jax.tree_util.tree_leaves(pool.cache)]


def test_corrupt_drafts_identical_output_and_kv_bytes(engine):
    """THE rollback pin: with every draft garbled (acceptance exactly 0)
    each round fully rejects, emits only the verify's own position-0
    sample, and commits lengths += 1 — output AND the committed target-KV
    prefix must be byte-equal to a never-drafted run (the garbage the
    verify wrote beyond the commit is dead state)."""
    prompts = _prompts(engine, 1, [8], seed=6)
    ref, s_ref = _run(engine, prompts, max_new=9)
    spec = SpecConfig(corrupt_drafts=True, cooldown_rounds=1,
                      max_collapses=100)   # keep probing: every round spec
    got, s = _run(engine, prompts, max_new=9, spec=spec)
    assert got == ref
    m = s.metrics
    assert m.spec_rounds > 0
    assert m.spec_tokens_accepted == 0          # total rejection
    assert m.spec_tokens_rejected == m.spec_tokens_drafted
    # committed KV prefix: prompt + outputs[:-1] (the last token is the
    # next input, never written)
    L = len(prompts[0]) + len(ref[0]) - 1
    for a, b in zip(_committed_kv(s_ref.pool, 0, L),
                    _committed_kv(s.pool, 0, L)):
        np.testing.assert_array_equal(a, b)


# ---------------------------------------------------------------------------
# K controller: collapse -> plain bursts, bounded probe cost
# ---------------------------------------------------------------------------
def test_k_controller_collapse_falls_back_to_plain(setup):
    """Collapsed acceptance (corrupt drafts) must degrade to the plain
    burst path: the planner halves K to 1, cools down with backoff,
    probes at K=1, and after max_collapses consecutive failures switches
    off permanently — total spec overhead is a bounded constant, so
    dispatches-per-token approaches the plain-burst rate as the run
    grows.  Pinned here as: dpt_spec <= dpt_plain + overhead/T with the
    overhead measured and itself bounded."""
    eng = make_engine(setup, max_len=120, n_slots=2)
    prompts = _prompts(eng, 1, [8], seed=7)
    spec = SpecConfig(k_init=4, k_max=4, corrupt_drafts=True,
                      cooldown_rounds=2, cooldown_backoff=2,
                      max_collapses=2)
    ref, s_plain = _run(eng, prompts, max_new=97)
    got, s = _run(eng, prompts, max_new=97, spec=spec)
    assert got == ref
    snap = s.spec_planner.snapshot()
    assert snap["off"] and snap["collapses"] == 2
    m = s.metrics
    # bounded probe cost: K halves k_init -> 1 (log2+1 rounds), then one
    # K=1 probe per further collapse
    max_rounds = spec.k_init.bit_length() + (spec.max_collapses - 1)
    assert m.spec_rounds <= max_rounds
    # plain bursts actually resumed at full K after the collapse
    assert any(k > 1 for k in m.burst_hist)
    rep, rep_p = m.report(), s_plain.metrics.report()
    overhead = (m.spec_draft_dispatches + m.spec_verify_dispatches
                + m.spec_catchup_dispatches)
    assert overhead <= 3 * max_rounds
    # + small slack for burst-ladder fragmentation around spec rounds
    assert rep["dispatches_per_token"] <= (
        rep_p["dispatches_per_token"]
        + (overhead + 3 * m.spec_rounds + 1) / rep["total_new_tokens"])


def test_planner_unit():
    """Pure controller mechanics: pow2/budget/capacity caps, EMA ladder,
    collapse backoff, permanent off, expected-tokens estimate."""
    cfg = SpecConfig(k_init=4, k_max=8, cooldown_rounds=2,
                     cooldown_backoff=2, max_collapses=2)
    p = SpecPlanner(cfg)

    class Pool:
        max_len, lengths = 64, {0: 10, 1: 20}

    class Req:
        def __init__(self, budget):
            self.sampling = SamplingParams(max_new_tokens=budget)
            self.n_generated = 0

    assert p.plan([(Req(10), 0)], Pool) == 4          # k_init
    assert p.plan([(Req(3), 0)], Pool) == 2           # budget-1 cap
    assert p.plan([(Req(1), 0)], Pool) == 0           # 1-token budget: plain
    tight = Pool()
    tight.lengths = {0: 61}
    assert p.plan([(Req(10), 0)], tight) == 2         # capacity 64-61-1, pow2
    # EMA ladder up at high acceptance
    p.observe(4, 4)
    assert p.k == 8 and p.ema == 1.0
    # collapse: halve to 1 over rounds, then cooldown
    for _ in range(8):
        p.observe(4, 0)
        if p.cooldown:
            break
    assert p.cooldown == 2 and p.k == 1 and p.ema is None
    assert not p.active
    assert p.plan([(Req(10), 0)], Pool) == 0 and p.cooldown == 1
    assert p.plan([(Req(10), 0)], Pool) == 0 and p.cooldown == 0
    # failed K=1 probe: second consecutive collapse -> off for good
    p.observe(1, 0)
    assert p.off and not p.active
    assert p.plan([(Req(10), 0)], Pool) == 0
    # expected tokens: geometric sum under the EMA
    q = SpecPlanner(SpecConfig(k_init=2, k_max=2))
    q.observe(2, 2)   # ema 1.0 -> clamped 0.999
    assert q.expected_tokens_per_round() == pytest.approx(3.0, abs=0.01)
    q2 = SpecPlanner(SpecConfig(k_init=2, k_max=2))
    q2.observe(2, 1)  # ema 0.5 -> 1 + 0.5 + 0.25
    assert q2.expected_tokens_per_round() == pytest.approx(1.75)


def test_accept_longest_prefix_unit():
    d = np.array([5, 6, 7])
    assert accept_longest_prefix(d, np.array([5, 6, 7, 8]), -1, 100) == (4, 3)
    assert accept_longest_prefix(d, np.array([5, 9, 7, 8]), -1, 100) == (2, 1)
    assert accept_longest_prefix(d, np.array([9, 6, 7, 8]), -1, 100) == (1, 0)
    # budget truncation caps both emitted and accepted
    assert accept_longest_prefix(d, np.array([5, 6, 7, 8]), -1, 2) == (2, 2)
    # EOS inside the window truncates emission at the EOS
    assert accept_longest_prefix(d, np.array([5, 6, 7, 8]), 6, 100) == (2, 2)
    assert accept_longest_prefix(d, np.array([9, 6, 7, 8]), 9, 100) == (1, 0)


def test_draft_engine_compute_twin_is_cached(engine):
    """Two DraftEngines over the same target and policy share ONE inner
    compute engine (jit reuse across warmup/timed schedulers) while
    keeping separate pool state."""
    a = DraftEngine(engine, SpecConfig())
    b = DraftEngine(engine, SpecConfig(corrupt_drafts=True))
    c = DraftEngine(engine, SpecConfig(draft_kv="fp8"))
    assert a.engine is b.engine
    assert c.engine is not a.engine
    assert a.pools is not b.pools


# ---------------------------------------------------------------------------
# EDF admission ordering (satellite)
# ---------------------------------------------------------------------------
def test_edf_orders_admission_within_priority_class(setup):
    """Within one priority class, a tighter absolute TTFT deadline
    (arrival + ttft_deadline_s) is admitted first even when it arrived
    later; deadline-free requests keep FCFS behind deadlined ones."""
    eng = make_engine(setup, n_slots=1)   # serialize admission
    prompts = _prompts(eng, 3, [6], seed=8)
    # submit order: A (occupies the slot), B loose (600s), C tight (300s)
    sched = Scheduler(eng)
    sp = SamplingParams(max_new_tokens=5)
    a = sched.submit(Request(prompt=prompts[0], sampling=sp))
    b = sched.submit(Request(prompt=prompts[1], sampling=sp,
                             ttft_deadline_s=600.0))
    c = sched.submit(Request(prompt=prompts[2], sampling=sp,
                             ttft_deadline_s=300.0))
    sched.run(max_steps=300)
    assert all(r.is_finished for r in (a, b, c))
    # C (tight) beat B (loose) to its first token despite arriving later
    assert c.first_token_time < b.first_token_time
    # FCFS preserved when nobody carries a deadline
    sched = Scheduler(eng)
    r1 = sched.submit(Request(prompt=prompts[0], sampling=sp))
    r2 = sched.submit(Request(prompt=prompts[1], sampling=sp))
    r3 = sched.submit(Request(prompt=prompts[2], sampling=sp))
    sched.run(max_steps=300)
    assert r1.first_token_time < r2.first_token_time < r3.first_token_time
    # priority classes still dominate deadlines entirely
    sched = Scheduler(eng)
    lo = sched.submit(Request(prompt=prompts[0], sampling=sp))
    bg = sched.submit(Request(prompt=prompts[1], sampling=sp, priority=5,
                              ttft_deadline_s=300.0))
    hi = sched.submit(Request(prompt=prompts[2], sampling=sp, priority=0,
                              ttft_deadline_s=600.0))
    sched.run(max_steps=300)
    assert hi.first_token_time < bg.first_token_time


# ---------------------------------------------------------------------------
# Accounting identities + observability lanes (satellites)
# ---------------------------------------------------------------------------
def test_spec_accounting_identities_and_registry(engine):
    """drafted == accepted + rejected; emitted == accepted + bonus with
    bonus <= one per row per round; every generated token is a prefill
    first token, a plain decode emission, or a spec emission; and the
    registry exposes the spec families."""
    from repro.obs import MetricsRegistry, Observability
    obs = Observability(registry=MetricsRegistry())
    sched = Scheduler(engine, obs=obs, spec=SpecConfig())
    sp = SamplingParams(temperature=0.6, max_new_tokens=17, seed=21)
    prompts = _prompts(engine, 3, [9, 6, 8], seed=9)
    reqs = [sched.submit(Request(prompt=p, sampling=sp)) for p in prompts]
    sched.run(max_steps=600)
    assert all(r.is_finished for r in reqs)
    m = sched.metrics
    assert m.spec_rounds > 0
    assert m.spec_tokens_drafted == (m.spec_tokens_accepted
                                     + m.spec_tokens_rejected)
    assert m.spec_tokens_emitted == (m.spec_tokens_accepted
                                     + m.spec_bonus_tokens)
    assert 0 < m.spec_bonus_tokens <= m.spec_rounds * engine.scfg.n_slots
    assert m.total_new_tokens == (len(m.ttft) + m.decode_tokens_emitted
                                  + m.spec_tokens_emitted)
    assert sum(k * v for k, v in m.spec_accept_hist.items()) \
        == m.spec_tokens_accepted
    rep = m.report()
    assert rep["spec"]["rounds"] == m.spec_rounds
    assert rep["spec"]["verify_dispatches"] == m.spec_verify_dispatches
    assert rep["dispatches_per_token"] > 0
    text = obs.registry.expose()
    for family in ("serve_spec_rounds_total", "serve_spec_dispatches_total",
                   "serve_spec_tokens_total",
                   "serve_spec_accepted_per_verify"):
        assert family in text, family


def test_spec_trace_lanes(engine, tmp_path):
    """Draft and verify dispatches land on their own trace lanes with
    planned-K and accepted-count args; a spec-off scheduler never
    registers the lanes (the byte-identical §13 trace pin stays intact)."""
    from repro.obs import Observability, Tracer
    obs = Observability(tracer=Tracer())
    sched = Scheduler(engine, obs=obs, spec=SpecConfig())
    sp = SamplingParams(max_new_tokens=13)
    req = sched.submit(Request(prompt=_prompts(engine, 1, [8], seed=10)[0],
                               sampling=sp))
    sched.run(max_steps=300)
    assert req.is_finished and sched.metrics.spec_rounds > 0
    import json
    path = tmp_path / "spec.trace.json"
    obs.tracer.write(str(path))
    events = json.loads(path.read_text())
    if isinstance(events, dict):          # either trace-event container
        events = events["traceEvents"]
    drafts = [e for e in events if e.get("name") == "spec_draft"]
    verifies = [e for e in events if e.get("name") == "spec_verify"]
    assert drafts and verifies
    assert {e["tid"] for e in drafts}.isdisjoint(
        {e["tid"] for e in verifies})
    for e in drafts:
        assert e["args"]["k"] >= 1
    for e in verifies:
        assert 0 <= e["args"]["accepted"] <= e["args"]["k"]
        assert 1 <= e["args"]["emitted"] <= e["args"]["k"] + 1
    names = [e for e in events if e.get("ph") == "M"
             and e.get("name") == "thread_name"]
    labels = {e["args"]["name"] for e in names}
    assert any(n.startswith("draft:") for n in labels)
    assert any(n.startswith("verify:") for n in labels)
    # spec-off: no spec lanes registered
    obs2 = Observability(tracer=Tracer())
    sched2 = Scheduler(engine, obs=obs2)
    r2 = sched2.submit(Request(prompt=_prompts(engine, 1, [8], seed=10)[0],
                               sampling=sp))
    sched2.run(max_steps=300)
    assert r2.is_finished
    path2 = tmp_path / "plain.trace.json"
    obs2.tracer.write(str(path2))
    events2 = json.loads(path2.read_text())
    if isinstance(events2, dict):
        events2 = events2["traceEvents"]
    labels2 = {e["args"]["name"] for e in events2 if e.get("ph") == "M"
               and e.get("name") == "thread_name"}
    assert not any(n.startswith(("draft:", "verify:")) for n in labels2)


def test_perfmodel_prices_draft_verify_pair():
    """The analytical model prices a spec round honestly: under the
    Table-III/IV slot deployment at batch 1 the MAC array has idle
    headroom, the K+1-position verify costs ~one plain step, and
    speculation wins wall clock; under the channel-streaming GEMV engine
    (throughput-matched to HBM by construction) extra verify positions
    cost linearly and speculation loses — the model must report both,
    monotone in acceptance."""
    from repro.perfmodel.analytical import spec_round_latency
    cfg = get_config("granite-8b")     # full-size paper geometry
    win = spec_round_latency(cfg, k=2, batch=1, context=512, acceptance=0.8,
                             use_engine_model=False)
    # idle-headroom regime: verify ~ a plain step, speculation pays
    assert win["t_verify_s"] < 1.1 * win["t_plain_per_token_s"]
    assert win["speedup"] > 1.0
    better = spec_round_latency(cfg, k=2, batch=1, context=512,
                                acceptance=0.95, use_engine_model=False)
    assert better["speedup"] > win["speedup"]
    # throughput-matched engine: no idle compute to hide the window in
    eng = spec_round_latency(cfg, k=2, batch=1, context=512, acceptance=0.8)
    assert eng["speedup"] < 1.0
    assert eng["t_verify_s"] <= 3 * eng["t_plain_per_token_s"] + 1e-12
    # acceptance monotonicity + geometric expected tokens
    low = spec_round_latency(cfg, k=4, batch=8, context=512, acceptance=0.1)
    high = spec_round_latency(cfg, k=4, batch=8, context=512, acceptance=0.8)
    assert low["speedup"] < high["speedup"]
    assert 1.0 <= low["expected_tokens_per_row"] \
        <= high["expected_tokens_per_row"] <= 5.0
