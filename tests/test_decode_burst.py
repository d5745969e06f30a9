"""Device-resident decode-burst tests (DESIGN.md §11).

The contract: a K-step burst (one jitted ``lax.scan``, one dispatch, one
host sync) emits exactly the tokens K fused single steps emit — greedy AND
seeded temperature sampling (same per-(request, step) key schedule) — and
the scheduler's burst planning never perturbs admission latency or
chunked-prefill interleaving (K clamps to 1 while either is pending).
Multi-device tests extend the dp x tp bit-identity contract (§10) to
bursts and run under CI's 8-forced-host-device job.
"""
import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.launch.mesh import make_mesh
from repro.models.common import InitMaker, QuantMaker
from repro.models import transformer as T
from repro.serve import (Request, RequestState, SamplingParams, ServeConfig,
                         ServingEngine, Scheduler)

multi_device = pytest.mark.skipif(
    len(jax.devices()) < 8,
    reason="needs XLA_FLAGS=--xla_force_host_platform_device_count=8")


@pytest.fixture(scope="module")
def engine():
    cfg = get_config("granite-8b", smoke=True)
    params = T.build_params(cfg, InitMaker(jax.random.PRNGKey(0)))
    return ServingEngine(cfg, params, ServeConfig(
        max_len=48, n_slots=4, prefill_chunk=8, max_burst=8))


def _prompts(engine, n, lens, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, engine.cfg.vocab, (lens[i % len(lens)],))
            .astype(np.int32) for i in range(n)]


def _run(engine, prompts, *, max_burst, max_new=6, temperature=0.0,
         seed=0, midflight=False):
    """One scheduler run; returns (per-request token lists, scheduler)."""
    sched = Scheduler(engine, max_burst=max_burst)
    sp = SamplingParams(temperature=temperature, max_new_tokens=max_new,
                        seed=seed)
    head = prompts[:-1] if midflight else prompts
    reqs = [sched.submit(Request(prompt=p, sampling=sp)) for p in head]
    if midflight:
        while sched.n_decode_steps < 2:
            sched.step()
        reqs.append(sched.submit(Request(prompt=prompts[-1], sampling=sp)))
    sched.run(max_steps=400)
    assert all(r.is_finished for r in reqs)
    return [list(r.output_tokens) for r in reqs], sched


# ---------------------------------------------------------------------------
# Bit-identity: burst == single-step, greedy and seeded temperature
# ---------------------------------------------------------------------------
def test_burst_bit_identical_to_single_step_greedy(engine):
    """THE burst contract: greedy tokens at max_burst=8 == max_burst=1,
    request for request — including a mid-flight admission, which forces
    K=1 rounds around the admission exactly like the burst-free path."""
    prompts = _prompts(engine, 4, [9, 6, 11, 8], seed=1)
    ref, s1 = _run(engine, prompts, max_burst=1, midflight=True)
    got, s8 = _run(engine, prompts, max_burst=8, midflight=True)
    assert got == ref
    # the burst run actually burst (fewer dispatches, same token-steps)
    assert s8.n_decode_dispatches < s1.n_decode_dispatches
    assert any(k > 1 for k in s8.metrics.burst_hist)
    assert all(k == 1 for k in s1.metrics.burst_hist)


def test_burst_bit_identical_seeded_temperature(engine):
    """Temperature sampling: the precomputed [K, n_slots, 2] key schedule
    reproduces each request's step_key() sequence, so sampled (not just
    greedy) tokens are bit-identical between burst and single-step."""
    prompts = _prompts(engine, 3, [8, 11, 6], seed=2)
    ref, _ = _run(engine, prompts, max_burst=1, temperature=0.8, seed=13)
    got, s8 = _run(engine, prompts, max_burst=8, temperature=0.8, seed=13)
    assert got == ref
    assert any(k > 1 for k in s8.metrics.burst_hist)
    # and a different seed actually changes the continuation (the keys are
    # live, not dead inputs)
    other, _ = _run(engine, prompts, max_burst=8, temperature=0.8, seed=14)
    assert other != ref


def test_step_keys_match_step_key_sequence():
    """request.step_keys(n) row t == step_key() at n_generated + t — the
    on-device key-schedule contract."""
    r = Request(prompt=np.arange(1, 5, dtype=np.int32),
                sampling=SamplingParams(seed=3))
    r.id = 7
    r.output_tokens = [11, 22]            # n_generated = 2
    sched = np.asarray(r.step_keys(4))
    assert sched.shape == (4, 2) and sched.dtype == np.uint32
    for t in range(4):
        want = Request(prompt=r.prompt, sampling=r.sampling)
        want.id = 7
        want.output_tokens = [0] * (2 + t)
        np.testing.assert_array_equal(sched[t], np.asarray(want.step_key()))
    # the scheduler's batched builder (one transfer for all temperature
    # rows) produces the same bits
    from repro.serve.sampling import batched_step_keys
    np.testing.assert_array_equal(batched_step_keys([3], [7], [2], 4)[0],
                                  sched)


# ---------------------------------------------------------------------------
# Engine primitive: burst == K fused single steps, EOS freeze
# ---------------------------------------------------------------------------
def test_engine_burst_primitive_matches_single_steps(engine):
    """Low-level: decode_burst(K=4) over a prefilled pool emits exactly the
    tokens 4 decode_slots calls emit, and commits the same lengths."""
    prompts = _prompts(engine, 2, [8, 11], seed=4)

    def prefill():
        pool = engine.new_pool()
        slots = [pool.alloc(), pool.alloc()]
        last = engine.prefill_into_slots(pool, slots, prompts)
        first = np.zeros((pool.n_slots,), np.int32)
        for s, l in zip(slots, last):
            first[s] = int(np.argmax(np.asarray(l)))
        return pool, slots, first

    n = engine.scfg.n_slots
    active = np.zeros((n,), bool)
    # single-step reference: caller commits lengths for active rows
    pool, slots, tokens = prefill()
    active[slots] = True
    ref = []
    cur = tokens.copy()
    for _ in range(4):
        out = engine.decode_slots(pool, cur)
        pool.lengths[active] += 1
        ref.append(out[slots].tolist())
        cur = np.where(active, out, cur)
    ref_lengths = pool.lengths.copy()

    pool2, slots2, tokens2 = prefill()
    keys = np.zeros((4, n, 2), np.uint32)
    toks, valid = engine.decode_burst(
        pool2, tokens2, keys, np.zeros((n,), np.float32), active,
        np.full((n,), 100, np.int32), np.full((n,), -1, np.int32))
    assert valid[:, slots2].all()
    assert [row[slots2].tolist() for row in toks] == ref
    np.testing.assert_array_equal(pool2.lengths, ref_lengths)


def test_burst_freezes_row_on_eos_and_scheduler_retires(engine):
    """A row sampling EOS mid-burst freezes on device (no further valid
    tokens, lengths stop advancing) and the scheduler retires it at the
    same position the single-step path would."""
    prompts = _prompts(engine, 1, [8], seed=5)
    probe, _ = _run(engine, prompts, max_burst=1, max_new=8)
    # EOS = the first token value NOT seen earlier in the sequence (so the
    # request cannot retire before it).  With max_new=16 a solo request's
    # first burst is planned K=8 (rem 15 -> pow2 8) and covers generated
    # tokens 2..9, so an EOS inside that window freezes the row strictly
    # mid-burst on device.
    seq = probe[0]
    i = next(j for j in range(1, len(seq)) if seq[j] not in seq[:j])
    assert i < 8, "probe sequence has no novel token inside the burst"
    eos = seq[i]
    sp = SamplingParams(max_new_tokens=16, eos_id=int(eos))

    def run(max_burst):
        sched = Scheduler(engine, max_burst=max_burst)
        req = sched.submit(Request(prompt=prompts[0], sampling=sp))
        sched.run(max_steps=100)
        return req, sched

    r1, _ = run(1)
    r8, s8 = run(8)
    assert r1.output_tokens == r8.output_tokens
    assert r8.finish_reason == r1.finish_reason == "eos"
    assert r8.n_generated == i + 1
    # the EOS landed mid-burst: planned token-steps exceed emitted tokens
    hist = s8.metrics.burst_hist
    assert any(k > 1 for k in hist)
    assert s8.metrics.decode_token_steps == sum(k * v for k, v in hist.items())
    assert s8.metrics.decode_token_steps > r8.n_generated - 1
    # slot returned to the pool despite the mid-burst freeze
    assert s8.pool.n_free == s8.pool.n_slots
    assert (s8.pool.lengths == 0).all()


# ---------------------------------------------------------------------------
# Scheduling semantics: admission / prefill force K = 1
# ---------------------------------------------------------------------------
def test_waiting_queue_and_prefill_force_single_steps(engine):
    """K > 1 bursts never run while the waiting queue is non-empty or a
    prefill is mid-flight (admission latency and chunked-prefill
    interleaving stay byte-identical to the burst-free scheduler)."""
    prompts = _prompts(engine, 6, [8, 6], seed=6)   # 6 requests, 4 slots
    sched = Scheduler(engine, max_burst=8)
    seen_ks = []
    orig = engine.decode_burst

    def checked(pool, tokens, key_schedule, *args, **kw):
        k = key_schedule.shape[0]
        seen_ks.append(k)
        if k > 1:
            assert not sched.waiting, "burst dispatched with queued work"
            assert not any(r.state is RequestState.PREFILL
                           for r in sched.running.values()), \
                "burst dispatched around a mid-flight prefill"
        return orig(pool, tokens, key_schedule, *args, **kw)

    reqs = [sched.submit(Request(
        prompt=p, sampling=SamplingParams(max_new_tokens=6)))
        for p in prompts]
    try:
        engine.decode_burst = checked
        sched.run(max_steps=400)
    finally:
        engine.decode_burst = orig
    assert all(r.is_finished for r in reqs)
    # the run exercised both regimes: queued-era K=1 rounds (hist) and
    # post-drain bursts
    assert 1 in sched.metrics.burst_hist
    assert any(k > 1 for k in seen_ks)
    # ... and output still matches the all-single-step run
    ref, _ = _run(engine, prompts, max_burst=1, max_new=6)
    assert [list(r.output_tokens) for r in reqs] == ref


def test_dispatch_count_regression(engine):
    """THE perf pin: decode jit entries per generated token must amortize
    to <= 1/K at max_burst=K for an uncontended decode run (monkeypatch-
    counted on the engine methods, independent of scheduler bookkeeping)."""
    prompts = _prompts(engine, 1, [8], seed=7)
    sp = SamplingParams(max_new_tokens=33)            # 8 + 33 <= 48

    def count(max_burst):
        calls = {"n": 0}
        orig_b, orig_s = engine.decode_burst, engine.decode_slots

        def wrap(orig):
            def inner(*a, **kw):
                calls["n"] += 1
                return orig(*a, **kw)
            return inner

        sched = Scheduler(engine, max_burst=max_burst)
        req = sched.submit(Request(prompt=prompts[0], sampling=sp))
        try:
            engine.decode_burst = wrap(orig_b)
            engine.decode_slots = wrap(orig_s)
            sched.run(max_steps=200)
        finally:
            engine.decode_burst, engine.decode_slots = orig_b, orig_s
        assert req.n_generated == 33
        return calls["n"], sched

    n1, s1 = count(1)
    n8, s8 = count(8)
    assert n1 == 32                          # first token comes off prefill
    assert n8 * 8 <= n1 + 7                  # <= ceil(n1 / 8): 1/K amortized
    assert n8 / 33 <= 1 / 8                  # dispatches per generated token
    # scheduler accounting agrees with the monkeypatch count
    assert s8.n_decode_dispatches == n8
    assert s8.n_decode_steps == s1.n_decode_steps == 32
    rep = s8.metrics.report()
    assert rep["decode_dispatches"] == n8
    assert rep["decode_dispatches_per_token"] <= 1 / 8
    assert rep["itl_granularity"] == "burst"
    assert s1.metrics.report()["itl_granularity"] == "token"


def test_burst_metrics_and_host_sync_accounting(engine):
    """Greedy host syncs = one per decode dispatch + two per request
    (final-chunk logits and the sampled first token); burst histogram keys
    are powers of two bounded by max_burst."""
    prompts = _prompts(engine, 3, [8, 11], seed=8)
    _, sched = _run(engine, prompts, max_burst=8, max_new=9)
    assert sched.n_host_syncs == sched.n_decode_dispatches + 2 * len(prompts)
    # temperature rows add exactly one (batched) key-schedule transfer per
    # decode round, not one per row
    _, tsched = _run(engine, prompts, max_burst=8, max_new=9,
                     temperature=0.7, seed=3)
    assert tsched.n_host_syncs == \
        2 * tsched.n_decode_dispatches + 2 * len(prompts)
    for k in sched.metrics.burst_hist:
        assert 1 <= k <= 8 and (k & (k - 1)) == 0
    rep = sched.metrics.report()
    assert rep["decode_token_steps"] == sched.n_decode_steps
    assert 0 < rep["decode_dispatches_per_token"] <= 1.0
    # every token except the per-request prefill-sampled first one was
    # emitted by a decode dispatch
    assert rep["decode_tokens_emitted"] == \
        rep["total_new_tokens"] - len(prompts)


def test_generate_reports_burst_accounting(engine):
    """The one-shot generate() wrapper surfaces the burst accounting of its
    private scheduler (consumed by launch/serve and the bench)."""
    prompts = _prompts(engine, 2, [8], seed=9)
    out = engine.generate({"tokens": np.stack(prompts)}, max_new_tokens=10)
    # 18 decode-emitted tokens (2 first tokens come off prefill) in far
    # fewer dispatches than token-steps
    assert out["decode_token_steps"] >= 9
    assert out["decode_dispatches"] < out["decode_token_steps"]
    assert out["host_syncs"] == out["decode_dispatches"] + 2 * 2
    assert any(k > 1 for k in out["burst_hist"])


# ---------------------------------------------------------------------------
# Sharded bursts (DESIGN.md §10 contract extended to §11)
# ---------------------------------------------------------------------------
def test_mesh_single_device_burst_bit_identical(engine):
    """A (1, 1) mesh walks the whole sharded burst path (explicit carry
    shardings, key-schedule sharding, donation) — fast-loop coverage."""
    cfg, params = engine.cfg, engine.params
    prompts = _prompts(engine, 3, [9, 6], seed=10)
    ref, _ = _run(engine, prompts, max_burst=8)
    mesh = make_mesh((1, 1), ("data", "model"))
    eng = ServingEngine(cfg, params, ServeConfig(
        max_len=48, n_slots=4, prefill_chunk=8, max_burst=8, mesh=mesh))
    got, sched = _run(eng, prompts, max_burst=8)
    assert got == ref
    assert any(k > 1 for k in sched.metrics.burst_hist)


@multi_device
def test_burst_dp2_tp4_bit_identical():
    """Bursts under the dp=2 x tp=4 mesh (8 forced host devices), quantized
    weights + int8 KV pool, mid-flight admission included: bit-identical to
    the single-device single-step run, with strictly fewer dispatches.
    Both run the Pallas kernels: ``kernel='auto'`` would pick jnp meshless
    and the kernels under the mesh, which round differently."""
    from repro.quant.policy import PrecisionPolicy
    cfg = get_config("granite-8b", smoke=True)
    params = T.build_params(cfg, QuantMaker(jax.random.PRNGKey(0)))
    rng = np.random.default_rng(11)
    prompts = [rng.integers(1, cfg.vocab, (n,)).astype(np.int32)
               for n in (9, 6, 11, 8)]

    def engine(mesh):
        return ServingEngine(cfg, params, ServeConfig(
            max_len=32, n_slots=8, prefill_chunk=8, max_burst=8, mesh=mesh,
            policy=PrecisionPolicy(kv="int8", kernel="pallas")))

    ref, s1 = _run(engine(None), prompts, max_burst=1, midflight=True)
    mesh = make_mesh((2, 4), ("data", "model"))
    got, s8 = _run(engine(mesh), prompts, max_burst=8, midflight=True)
    assert got == ref
    assert s8.n_decode_dispatches < s1.n_decode_dispatches
    assert any(k > 1 for k in s8.metrics.burst_hist)
    assert s8.metrics.report()["topology"] == \
        {"n_devices": 8, "dp": 2, "tp": 4}
