"""Observability-layer tests (DESIGN.md §13).

Three contracts:

1. **Zero-overhead when disabled**: ``Scheduler(engine)`` with no obs
   bundle makes exactly the baseline number of host syncs, dispatches
   AND clock calls — attaching observability must never have been able
   to perturb the un-observed hot path.
2. **Determinism**: under a virtual clock, two identical runs produce
   byte-identical Chrome trace files and identical registry snapshots.
3. **Schema stability**: the metrics report and trace event key sets are
   pinned, and every report is RFC-JSON clean (``allow_nan=False``
   round-trips) — downstream join scripts (CI artifact checks,
   benchmarks/BENCH_serve_baseline.json comparisons) key on both.
"""
import json

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.models.common import InitMaker
from repro.models import transformer as T
from repro.launch.hlo_analysis import compiled_step_cost
from repro.obs import (Counter, Gauge, Histogram, MetricsRegistry,
                       Observability, PID_REQUESTS, PID_SCHEDULER,
                       SnapshotWriter, Tracer, span)
from repro.serve import (Request, SamplingParams, ServeConfig, ServingEngine,
                         Scheduler)
from repro.serve.metrics import ServeMetrics, burst_spread_itl


@pytest.fixture(scope="module")
def engine():
    cfg = get_config("granite-8b", smoke=True)
    params = T.build_params(cfg, InitMaker(jax.random.PRNGKey(0)))
    return ServingEngine(cfg, params, ServeConfig(
        max_len=48, n_slots=4, prefill_chunk=8, max_burst=8))


def _prompts(engine, lens, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, engine.cfg.vocab, (n,)).astype(np.int32)
            for n in lens]


class VirtualClock:
    """Deterministic ticking clock: every call advances by ``dt``."""

    def __init__(self, dt=0.125):
        self.now = 0.0
        self.dt = dt
        self.calls = 0

    def __call__(self):
        self.calls += 1
        self.now += self.dt
        return self.now


def _run(engine, *, obs=None, clock=None, max_burst=8, n=3, max_new=7,
         temperature=0.0, tiers=None):
    clock = clock or VirtualClock()
    sched = Scheduler(engine, clock=clock, max_burst=max_burst, obs=obs,
                      tiers=tiers)
    for i, p in enumerate(_prompts(engine, [9, 6, 11, 8, 7][:n], seed=3)):
        sched.submit(Request(
            prompt=p,
            kv_policy=tiers[i % len(tiers)] if tiers else None,
            sampling=SamplingParams(temperature=temperature,
                                    max_new_tokens=max_new, seed=0)))
    sched.run(max_steps=400)
    return sched, clock


# ---------------------------------------------------------------------------
# registry primitives
# ---------------------------------------------------------------------------
def test_registry_counter_gauge_histogram():
    reg = MetricsRegistry()
    c = reg.counter("req_total", "requests")
    c.inc()
    c.inc(2, tier="int8")
    g = reg.gauge("depth", "queue depth")
    g.set(3)
    g.set(1)                                     # gauges overwrite
    h = reg.histogram("lat_s", "latency", buckets=(0.1, 1.0))
    for v in (0.05, 0.5, 5.0):
        h.observe(v)
    assert c.value() == 1 and c.value(tier="int8") == 2
    assert g.value() == 1
    # get-or-create: same family back, kind-checked
    assert reg.counter("req_total") is c
    with pytest.raises(ValueError, match="already registered"):
        reg.gauge("req_total")

    text = reg.expose()
    assert "# TYPE req_total counter" in text
    assert 'req_total{tier="int8"} 2' in text
    assert "# TYPE lat_s histogram" in text
    # cumulative buckets + +Inf + sum/count
    assert 'lat_s_bucket{le="0.1"} 1' in text
    assert 'lat_s_bucket{le="1"} 2' in text
    assert 'lat_s_bucket{le="+Inf"} 3' in text
    assert "lat_s_count 3" in text
    assert isinstance(reg.get("depth"), Gauge)
    assert isinstance(reg.get("lat_s"), Histogram)
    assert isinstance(c, Counter)


def test_counters_only_go_up():
    c = MetricsRegistry().counter("n", "")
    with pytest.raises(AssertionError):
        c.inc(-1)


def test_snapshot_writer(tmp_path):
    reg = MetricsRegistry()
    c = reg.counter("n", "")
    path = tmp_path / "snap.jsonl"
    w = SnapshotWriter(reg, str(path), every_s=1.0)
    assert w.maybe_write(0.0)                    # first call always writes
    c.inc()
    assert not w.maybe_write(0.5)                # interval not elapsed
    assert w.maybe_write(1.5)
    lines = [json.loads(s) for s in path.read_text().splitlines()]
    assert [s["ts"] for s in lines] == [0.0, 1.5]
    assert lines[0]["metrics"]["n"] == []        # no labelset touched yet
    assert lines[1]["metrics"]["n"][0]["value"] == 1
    assert w.n_written == 2


# ---------------------------------------------------------------------------
# tracer: format + determinism
# ---------------------------------------------------------------------------
def test_tracer_chrome_format_valid_json():
    tr = Tracer()
    tr.process_name(PID_SCHEDULER, "scheduler")
    tr.thread_name(PID_SCHEDULER, 0, "prefill")
    tr.complete("decode_burst", 1.0, 1.5, pid=PID_SCHEDULER, tid=1,
                args={"k": 4})
    tr.instant("first_token", 1.25, pid=PID_REQUESTS, tid=7)
    tr.counter("queue_depth", 1.5, {"waiting": 2})
    txt = tr.to_json()
    events = json.loads(txt)                     # closed, valid JSON array
    assert len(events) == len(tr) == 5
    # one self-contained JSON object per line (greppable)
    body = txt.strip().splitlines()[1:-1]
    assert all(json.loads(line.rstrip(",")) for line in body)
    x = next(e for e in events if e["ph"] == "X")
    assert (x["ts"], x["dur"]) == (1.0e6, 0.5e6)     # microseconds
    assert x["args"]["k"] == 4
    assert {e["ph"] for e in events} == {"M", "X", "i", "C"}
    # metadata dedup: naming the same lane twice emits once
    tr.thread_name(PID_SCHEDULER, 0, "prefill")
    assert len(tr) == 5


def test_trace_byte_identical_across_virtual_clock_runs(engine, tmp_path):
    """THE determinism contract: two identical virtual-clock runs write
    byte-identical trace files (and identical registry expositions)."""
    outs = []
    for name in ("a", "b"):
        obs = Observability(tracer=Tracer(), registry=MetricsRegistry())
        _run(engine, obs=obs)
        p = tmp_path / f"{name}.trace.json"
        obs.tracer.write(str(p))
        outs.append((p.read_bytes(), obs.registry.expose()))
    assert outs[0][0] == outs[1][0]
    assert outs[0][1] == outs[1][1]


def test_trace_carries_request_spans_and_dispatch_events(engine):
    obs = Observability(tracer=Tracer())
    sched, _ = _run(engine, obs=obs)
    events = json.loads(obs.tracer.to_json())
    req_spans = [e for e in events
                 if e["ph"] == "X" and e["pid"] == PID_REQUESTS]
    names = {e["name"] for e in req_spans}
    assert names == {"WAITING", "PREFILL", "DECODE"}
    # one full span triple per retired request, on the request's own tid
    for r in sched.finished:
        mine = [e for e in req_spans if e["tid"] == r.id]
        assert {e["name"] for e in mine} == {"WAITING", "PREFILL", "DECODE"}
        dec = next(e for e in mine if e["name"] == "DECODE")
        assert dec["args"]["n_generated"] == r.n_generated
    # per-dispatch events on the scheduler process with burst metadata
    bursts = [e for e in events if e["name"] == "decode_burst"]
    assert bursts and any(e["args"]["k"] > 1 for e in bursts)
    assert all(set(e["args"]) >= {"tier", "k", "rows", "slots", "dispatch"}
               for e in bursts)
    chunks = [e for e in events if e["name"] == "prefill_chunk"]
    assert chunks and all(e["tid"] == 0 for e in chunks)
    assert sum(e["args"]["final"] for e in chunks) == len(sched.finished)
    # counter tracks sampled each step
    assert any(e["ph"] == "C" and e["name"] == "queue_depth"
               for e in events)


# ---------------------------------------------------------------------------
# spans: the profiler sink and the Chrome sink of one helper
# ---------------------------------------------------------------------------
def test_span_writes_chrome_event_only_with_a_tracer():
    """Without a tracer a span reads no clock; with one it writes its
    Chrome event on that clock, with the args set before it closed, and
    identical runs give identical bytes."""
    clock = VirtualClock()
    with span("serve.decode", None, clock, event="decode_burst",
              k=4) as sp:
        sp.set(emitted=3)
    assert clock.calls == 0
    outs = []
    for _ in range(2):
        tr, clock = Tracer(), VirtualClock()
        with span("serve.step", step=7):
            with span("serve.decode", tr, clock, event="decode_burst",
                      pid=PID_SCHEDULER, tid=1, k=4,
                      slots=[0, 2]) as sp:
                sp.set(emitted=3)
            with span("serve.emit", tr, clock, tokens=3):   # no event
                pass
        outs.append(tr.to_json())
    assert outs[0] == outs[1]
    (evt,) = json.loads(outs[0])
    assert evt["name"] == "decode_burst" and evt["ts"] == 0.125e6
    assert evt["dur"] == 0.125e6
    assert evt["args"] == {"k": 4, "slots": [0, 2], "emitted": 3}


def _profiled_run(engine, tmp_path):
    """A scheduler run under a CPU ``jax.profiler`` trace; returns the run
    and the serve.* events of the thread that ran it, as
    (name, start_ns, end_ns, args)."""
    from jax.profiler import ProfileData
    jax.profiler.start_trace(str(tmp_path))
    try:
        sched, _ = _run(engine, temperature=0.7)
    finally:
        jax.profiler.stop_trace()
    (path,) = list(tmp_path.rglob("*.xplane.pb"))
    for plane in ProfileData.from_file(str(path)).planes:
        for line in plane.lines:
            evs = [(e.name, e.start_ns, e.end_ns, dict(e.stats))
                   for e in line.events if e.name.startswith("serve.")]
            if any(e[0] == "serve.step" for e in evs):
                return sched, evs
    raise AssertionError("no serve.step span in the trace")


def test_scheduler_spans_nest_in_the_profiler_trace(engine, tmp_path):
    """Every round is one ``serve.step`` (its step number as ``step_num``)
    holding its admit / prefill / decode / emit phases; every blocking
    device->host read (``serve.sync``) lies inside a dispatch span; the
    dispatch spans carry the request and dispatch ids."""
    sched, evs = _profiled_run(engine, tmp_path)
    steps = [e for e in evs if e[0] == "serve.step"]
    assert [e[3]["step_num"] for e in steps] == list(range(sched.n_steps))

    def inside(e, outer):
        return any(o[1] <= e[1] and e[2] <= o[2] for o in outer)

    by = {n: [e for e in evs if e[0] == n] for n in
          ("serve.admit", "serve.prefill", "serve.decode", "serve.sync",
           "serve.emit")}
    assert all(by.values()), {n: len(v) for n, v in by.items()}
    for name in ("serve.admit", "serve.prefill", "serve.decode",
                 "serve.emit"):
        assert all(inside(e, steps) for e in by[name]), name
    dispatches = by["serve.prefill"] + by["serve.decode"]
    assert all(inside(e, dispatches) for e in by["serve.sync"])
    assert {e[3]["what"] for e in by["serve.sync"]} == \
        {"logits", "first_token", "tokens", "keys"}
    assert len(by["serve.decode"]) == sched.n_decode_dispatches
    assert sum(e[3]["final"] for e in by["serve.prefill"]) == \
        len(sched.finished)
    assert {e[3]["req"] for e in by["serve.prefill"]} == \
        {r.id for r in sched.finished}
    ids = [e[3]["dispatch"] for e in dispatches]
    assert len(set(ids)) == len(ids) and max(ids) == sched._dispatch_seq
    assert sum(e[3]["tokens"] for e in by["serve.emit"]) + \
        len(sched.finished) == sum(r.n_generated for r in sched.finished)
    assert all(e[3]["admitted"] >= 1 for e in by["serve.admit"][:1])


# ---------------------------------------------------------------------------
# the zero-overhead guard (acceptance criterion)
# ---------------------------------------------------------------------------
def test_disabled_obs_is_noop_and_enabled_changes_nothing(engine):
    """obs=None adds NOTHING to the hot path: host syncs follow the PR-5
    baseline formula, clock calls are exactly the baseline set (submit +
    per-token emit + per-step sample), and enabling full observability
    changes neither tokens, syncs, dispatches nor step count."""
    base, base_clk = _run(engine)
    n_req = len(base.finished)
    n_tok = sum(r.n_generated for r in base.finished)
    # greedy baseline sync accounting (pinned since the burst PR):
    # one per decode dispatch + 2 per request (final chunk + first token)
    assert base.n_host_syncs == base.n_decode_dispatches + 2 * n_req
    # clock-call accounting: submit (1/request) + _emit (1/token) +
    # step-end metrics sample (1/step) — nothing else may touch the clock
    assert base_clk.calls == n_req + n_tok + base.n_steps

    obs = Observability(tracer=Tracer(), registry=MetricsRegistry())
    full, _ = _run(engine, obs=obs)
    assert [r.output_tokens for r in full.finished] == \
        [r.output_tokens for r in base.finished]
    assert full.n_host_syncs == base.n_host_syncs
    assert full.n_decode_dispatches == base.n_decode_dispatches
    assert full.n_steps == base.n_steps
    assert full.metrics.burst_hist == base.metrics.burst_hist
    # and the observed run actually observed
    assert len(obs.tracer) > 0


def test_token_dispatch_ids_recorded_without_obs(engine):
    """Dispatch attribution (the burst-spread ITL input) is always on:
    tokens of one burst share an id, ids are monotone, and the disabled
    path records them identically to the enabled one."""
    sched, _ = _run(engine)
    for r in sched.finished:
        assert len(r.token_dispatches) == r.n_generated
        assert all(d > 0 for d in r.token_dispatches)
        assert r.token_dispatches == sorted(r.token_dispatches)
    # with bursts, some request must have >1 token from one dispatch
    assert any(len(set(r.token_dispatches)) < r.n_generated
               for r in sched.finished)


# ---------------------------------------------------------------------------
# ServeMetrics edge cases (satellites)
# ---------------------------------------------------------------------------
def _req_stub(**kw):
    class R:
        id = 0
        tier = kw.get("tier")
        finish_reason = kw.get("finish_reason", "length")
        arrival_time = kw.get("arrival_time")
        first_token_time = kw.get("first_token_time")
        finish_time = kw.get("finish_time")
        token_times = kw.get("token_times", [])
        token_dispatches = kw.get("token_dispatches", [])
        n_generated = kw.get("n_generated", 0)
    return R()


def test_zero_wall_report_is_json_clean():
    """The old report emitted float('nan') for tokens_per_s at wall==0 —
    not RFC JSON.  Now: null, and the whole report round-trips with
    allow_nan=False."""
    m = ServeMetrics(4)
    m.on_arrival(1.0)
    m.on_finish(_req_stub(arrival_time=1.0, finish_time=1.0, n_generated=0))
    rep = m.report()
    assert rep["wall_s"] == 0.0
    assert rep["tokens_per_s"] is None
    assert json.loads(json.dumps(rep, allow_nan=False)) == rep


def test_report_json_roundtrip_from_real_run(engine):
    sched, _ = _run(engine, temperature=0.7)
    rep = sched.metrics.report()
    assert json.loads(json.dumps(rep, allow_nan=False)) == rep


def test_multi_tier_occupancy_weighting():
    """Per-tier occupancy weights each tier by ITS slot count: 1/2 int8
    slots busy is 0.5 for int8 even while the 6-slot total reads 3/6."""
    m = ServeMetrics(6)
    m.tiers = {"bf16": 4, "int8": 2}
    m.on_step(0.0, {"bf16": 2, "int8": 1})       # first sample: no weight
    m.on_step(1.0, {"bf16": 2, "int8": 1})       # [0,1): 2/4, 1/2
    m.on_step(3.0, {"bf16": 4, "int8": 0})       # [1,3): 4/4, 0/2
    rep = m.report()
    assert rep["slot_occupancy_mean"] == round((1 * 3 / 6 + 2 * 4 / 6) / 3, 4)
    assert rep["tier_occupancy_mean"] == {
        "bf16": round((1 * 0.5 + 2 * 1.0) / 3, 4),
        "int8": round((1 * 0.5 + 2 * 0.0) / 3, 4)}


def test_burst_histogram_mixed_k():
    m = ServeMetrics(4)
    for _ in range(3):
        m.on_decode_burst(1, 2, tier="bf16")
    for _ in range(2):
        m.on_decode_burst(8, 14, tier="bf16")
    rep = m.report()
    assert rep["burst_hist"] == {"1": 3, "8": 2}
    assert rep["decode_dispatches"] == 5
    assert rep["decode_token_steps"] == 3 + 16
    assert rep["decode_tokens_emitted"] == 6 + 28
    assert rep["itl_granularity"] == "burst"
    m2 = ServeMetrics(4)
    m2.on_decode_burst(1, 1)
    assert m2.report()["itl_granularity"] == "token"


def test_burst_spread_itl_math():
    # two bursts of 4 at t=1 (dispatch 7) and t=2 (dispatch 9): raw gaps
    # are [0,0,0,1,0,0,0]; spread: intra-first-burst gaps stay ~0 (3
    # samples of 0/3), the second burst's 1s wall spreads over 4 tokens
    times = [1.0] * 4 + [2.0] * 4
    disp = [7] * 4 + [9] * 4
    out = burst_spread_itl(times, disp)
    assert len(out) == len(times) - 1            # sample count == raw gaps
    assert out == [0.0] * 3 + [0.25] * 4
    # K=1 everywhere: spread IS the raw diff sequence
    times = [0.0, 0.5, 1.5]
    assert burst_spread_itl(times, [1, 2, 3]) == [0.5, 1.0]
    # missing dispatch ids: degrade to raw diffs
    assert burst_spread_itl(times, []) == [0.5, 1.0]


def test_itl_burst_spread_reported_alongside_raw(engine):
    """satellite (c): burst runs report both the raw (burst-granular)
    percentiles and the spread estimate; with max_burst=1 the two
    populations coincide and itl_granularity stays 'token'."""
    burst, _ = _run(engine)
    rep = burst.metrics.report()
    assert rep["itl_granularity"] == "burst"
    assert rep["itl_burst_spread_p95_s"] <= rep["itl_p95_s"]
    assert rep["itl_burst_spread_mean_s"] > 0
    single, _ = _run(engine, max_burst=1)
    rep1 = single.metrics.report()
    assert rep1["itl_granularity"] == "token"
    assert rep1["itl_burst_spread_p50_s"] == rep1["itl_p50_s"]
    assert rep1["itl_burst_spread_mean_s"] == rep1["itl_mean_s"]


def test_serve_metrics_publishes_into_registry(engine):
    reg = MetricsRegistry()
    sched, _ = _run(engine, obs=Observability(registry=reg))
    n_req = len(sched.finished)
    assert reg.get("serve_requests_arrived_total").value() == n_req
    assert reg.get("serve_requests_finished_total").value(
        tier="bf16", reason="length") == n_req
    assert reg.get("serve_decode_dispatches_total").value(tier="bf16") == \
        sched.n_decode_dispatches
    assert reg.get("serve_host_syncs_total").value() == sched.n_host_syncs
    assert reg.get("serve_admissions_total").value(tier="bf16") == n_req
    assert reg.get("serve_scheduler_steps_total").value() == sched.n_steps
    assert reg.get("serve_slots_total").value(tier="bf16") == 4
    assert reg.get("serve_queue_depth").value() == 0      # drained
    text = reg.expose()
    assert "# TYPE serve_burst_k histogram" in text


# ---------------------------------------------------------------------------
# compiled step cost
# ---------------------------------------------------------------------------
def test_compiled_step_cost(engine):
    pool = engine.new_pool()
    cost = compiled_step_cost(engine, pool)
    assert cost["k"] == 1 and cost["n_slots"] == pool.n_slots
    assert cost["flops"] > 0 and cost["hbm_bytes"] > 0
    assert cost["flops_per_token_step"] == round(
        cost["flops"] / pool.n_slots, 1)


# ---------------------------------------------------------------------------
# schema stability (CI keys on these)
# ---------------------------------------------------------------------------
REPORT_KEYS = {
    "n_requests", "total_new_tokens", "wall_s", "tokens_per_s",
    "slot_occupancy_mean", "decode_dispatches", "decode_token_steps",
    "decode_tokens_emitted", "decode_dispatches_per_step",
    "decode_dispatches_per_token", "burst_hist", "itl_granularity",
    # spec-aware amortization across both decode paths (DESIGN.md §17);
    # equals decode_dispatches_per_token when speculation is off
    "dispatches_per_token",
    "ttft_mean_s", "ttft_p50_s", "ttft_p95_s",
    "itl_mean_s", "itl_p50_s", "itl_p95_s",
    "e2e_latency_mean_s", "e2e_latency_p50_s", "e2e_latency_p95_s",
    "itl_burst_spread_mean_s", "itl_burst_spread_p50_s",
    "itl_burst_spread_p95_s",
    "finish_reasons", "queue_wait_p50_s", "queue_wait_p95_s",
}

TRACE_EVENT_KEYS = {
    "M": {"ph", "name", "pid", "tid", "args"},
    "X": {"ph", "name", "cat", "pid", "tid", "ts", "dur", "args"},
    "i": {"ph", "s", "name", "cat", "pid", "tid", "ts", "args"},
    "C": {"ph", "name", "cat", "pid", "tid", "ts", "args"},
}


def test_report_schema_stable(engine):
    sched, _ = _run(engine)
    assert set(sched.metrics.report()) == REPORT_KEYS
    # single-tier reports never carry tier keys; topology is None here
    mt, _ = _run(engine, tiers=["bf16", "int8"])
    assert set(mt.metrics.report()) == \
        REPORT_KEYS | {"tiers", "tier_occupancy_mean"}


def test_trace_schema_stable(engine):
    obs = Observability(tracer=Tracer())
    _run(engine, obs=obs)
    for e in json.loads(obs.tracer.to_json()):
        allowed = TRACE_EVENT_KEYS[e["ph"]]
        assert set(e) <= allowed, (e["ph"], set(e) - allowed)
        assert set(e) >= allowed - {"args"}
