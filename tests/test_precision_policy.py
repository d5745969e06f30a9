"""PrecisionPolicy: the unified datatype-adaptive contract (DESIGN.md §12).

Four tiers of coverage:
  * policy object semantics — JSON round-trip (identical resolved plan),
    first-match-wins resolution, legacy-adapter equivalence;
  * EAGER validation — unknown scheme / KV tier / kernel names and
    config/mesh incompatibilities raise at policy / ServeConfig / engine
    construction with actionable messages, not at first pool build or
    first trace (regression: these used to surface as KeyErrors or
    asserts deep in the first ``new_pool()`` / checkpoint build);
  * legacy-adapter bit-identity — ``ServeConfig(kv_dtype=...)`` /
    ``ServingEngine(plan=...)`` produce byte-identical output to the
    equivalent ``policy=`` spelling (single-device here; the dp=2 x tp=4
    twin runs in CI's multi-device job);
  * runtime tier switching — ONE engine serves bf16-KV and int8-KV
    requests interleaved (mid-flight admission included), each tier's
    output bit-identical to a single-tier engine at that precision, and
    budget-derived tier pools show the quantized-capacity win.
"""
import dataclasses

import jax
import numpy as np
import pytest
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro.configs import get_config
from repro.launch.mesh import make_mesh
from repro.models.common import QuantMaker
from repro.models import transformer as T
from repro.quant.policy import PrecisionPolicy, validate_kv_tier
from repro.runtime import partitioning as PT
from repro.serve import (Request, SamplingParams, Scheduler, ServeConfig,
                         ServingEngine)

multi_device = pytest.mark.skipif(
    len(jax.devices()) < 8,
    reason="needs XLA_FLAGS=--xla_force_host_platform_device_count=8")


def _amesh(dp, tp):
    return AbstractMesh((dp, tp), ("data", "model"))


# ---------------------------------------------------------------------------
# Policy object semantics
# ---------------------------------------------------------------------------
def test_policy_json_roundtrip_identical_resolved_plan():
    cfg = get_config("granite-8b", smoke=True)
    p = PrecisionPolicy(weights={"attn.*": "mxfp4", "ffn.w_down": "bf16"},
                        kv="int8", kernel="jnp")
    q = PrecisionPolicy.from_json(p.to_json())
    assert q == p and hash(q) == hash(p)     # frozen: usable as a cache key
    assert q.resolved_plan(cfg) == p.resolved_plan(cfg)
    # the resolved plan is concrete: every dense leaf maps to a scheme name
    plan = p.resolved_plan(cfg)
    assert plan["attn.wq"] == "mxfp4"
    assert plan["ffn.w_down"] == "bf16"
    assert plan["ffn.w_up"] == "awq_int4"        # config default untouched
    assert plan["lm_head"] == "bf16"             # dense leaves read 'bf16'


def test_policy_first_match_wins():
    p = PrecisionPolicy(weights=(("attn.wq", "w8a8"), ("attn.*", "fp8")))
    assert p.resolve("attn.wq") == "w8a8"
    assert p.resolve("attn.wk") == "fp8"
    assert p.resolve("ffn.w_up", "awq_int4") == "awq_int4"
    assert p.resolve("ffn.w_up") == "bf16"       # no default: dense


def test_legacy_adapters_emit_equivalent_policy():
    cfg = get_config("granite-8b", smoke=True)
    plan = {"ffn.w_down": "bf16"}
    via_legacy = PrecisionPolicy.from_legacy(kv_dtype="int8", plan=plan)
    via_policy = PrecisionPolicy(weights=tuple(plan.items()), kv="int8")
    assert via_legacy.resolved_plan(cfg) == via_policy.resolved_plan(cfg)
    # ServeConfig(kv_dtype=...) is the same adapter, canonicalized
    scfg = ServeConfig(max_len=32, kv_dtype="int8")
    assert scfg.policy.kv == "int8" and scfg.kv_dtype == "int8"
    assert ServeConfig(max_len=32).kv_dtype == "bf16"
    import jax.numpy as jnp
    assert ServeConfig(max_len=32, kv_dtype=jnp.bfloat16).kv_dtype == "bf16"
    # a non-bf16 raw dtype is rejected, not silently coerced to a tier
    with pytest.raises(ValueError, match="not expressible"):
        ServeConfig(max_len=32, kv_dtype=jnp.float32)


def test_param_specs_from_policy_match_plan_spelling():
    cfg = get_config("granite-8b", smoke=True)
    pol = PrecisionPolicy(weights={"ffn.w_down": "bf16", "attn.wq": "mxfp4"})
    mesh = _amesh(1, 4)
    via_policy = PT.param_specs(cfg, mesh, train=False, quantize=True,
                                policy=pol)
    via_plan = PT.param_specs(cfg, mesh, train=False, quantize=True,
                              plan=pol.resolved_plan(cfg))
    assert jax.tree_util.tree_structure(
        via_policy, is_leaf=lambda x: isinstance(x, P)) == \
        jax.tree_util.tree_structure(
            via_plan, is_leaf=lambda x: isinstance(x, P))
    assert jax.tree_util.tree_leaves(
        via_policy, is_leaf=lambda x: isinstance(x, P)) == \
        jax.tree_util.tree_leaves(
            via_plan, is_leaf=lambda x: isinstance(x, P))
    with pytest.raises(ValueError, match="not both"):
        PT.param_specs(cfg, mesh, train=False, plan={}, policy=pol)


# ---------------------------------------------------------------------------
# Eager validation (regression: used to fail at first pool build / trace)
# ---------------------------------------------------------------------------
def test_unknown_scheme_raises_at_policy_construction():
    with pytest.raises(ValueError, match="valid schemes"):
        PrecisionPolicy(weights={"attn.*": "int5"})


def test_unknown_kernel_raises_at_policy_construction():
    with pytest.raises(ValueError, match="valid modes"):
        PrecisionPolicy(kernel="cuda")


def test_unknown_kv_tier_raises_at_serveconfig_construction():
    """Previously an unknown kv_dtype was a KeyError at the FIRST
    ``engine.new_pool()`` (deep in init_cache); now it is a ValueError at
    ServeConfig construction, naming the valid tiers."""
    with pytest.raises(ValueError, match="valid tiers"):
        ServeConfig(max_len=32, kv_dtype="int88")
    with pytest.raises(ValueError, match="valid tiers"):
        PrecisionPolicy(kv="fp16")


def test_policy_kv_conflicting_legacy_knob_raises():
    with pytest.raises(ValueError, match="contradicts"):
        ServeConfig(max_len=32, kv_dtype="bf16",
                    policy=PrecisionPolicy(kv="int8"))
    # agreeing spellings are fine
    ServeConfig(max_len=32, kv_dtype="int8",
                policy=PrecisionPolicy(kv="int8"))


def test_unmatched_pattern_raises_at_engine_construction():
    cfg = get_config("granite-8b", smoke=True)
    params = T.build_params(cfg, QuantMaker(jax.random.PRNGKey(0)))
    bad = ServeConfig(max_len=32,
                      policy=PrecisionPolicy(weights={"moe.*": "fp8"}))
    with pytest.raises(ValueError, match="matches no leaf"):
        ServingEngine(cfg, params, bad)      # granite-smoke has no MoE


def test_group_indivisible_k_raises_eagerly():
    """A scheme whose scale group does not divide a leaf's K used to die
    in an assert inside the offline quantizer at checkpoint build; the
    policy names the leaf and the conflict up front."""
    cfg = dataclasses.replace(get_config("granite-8b", smoke=True), d_ff=48)
    pol = PrecisionPolicy(weights={"ffn.w_down": "mxfp4"})   # group 32, K=48
    with pytest.raises(ValueError, match="scale group"):
        pol.validate_for(cfg)


def test_quantized_kv_on_mla_raises_eagerly():
    """MLA latents stay bf16 (DESIGN.md §9): the tier conflict used to
    surface at first pool build (mla_cache_spec); now at policy/engine
    validation — and per-pool tier overrides hit the same check."""
    cfg = get_config("deepseek-v2-236b", smoke=True)
    with pytest.raises(ValueError, match="MLA"):
        PrecisionPolicy(kv="int8").validate_for(cfg)
    with pytest.raises(ValueError, match="MLA"):
        validate_kv_tier("fp8", cfg)
    assert validate_kv_tier("bf16", cfg) == "bf16"


def test_pallas_kernel_validates_under_multi_device_mesh():
    """kernel='pallas' is now first-class under a mesh: the kernels run
    shard_map'd over it (DESIGN.md §14) with per-site jnp fallback, so the
    old eager GSPMD rejection is gone for every mesh shape."""
    cfg = get_config("granite-8b", smoke=True)
    pol = PrecisionPolicy(kernel="pallas")
    assert pol.validate_for(cfg, _amesh(1, 2)) is pol
    pol.validate_for(cfg, _amesh(2, 4))
    pol.validate_for(cfg, _amesh(1, 1))      # single device: allowed
    pol.validate_for(cfg)                    # meshless: allowed


def test_strict_tp_packed_k_grouping_raises():
    """tp-incompatible packed-K groupings: at tp=64 the full granite
    config's per-shard K (e.g. w_down: 14336/64 = 224) splits awq_int4's
    128-wide scale groups — strict validation raises at policy-resolution
    time instead of silently replicating the leaf."""
    cfg = get_config("granite-8b")
    pol = PrecisionPolicy()
    pol.validate_for(cfg, _amesh(1, 8), strict_tp=True)     # aligned: ok
    with pytest.raises(ValueError, match="scale group"):
        pol.validate_for(cfg, _amesh(1, 64), strict_tp=True)
    # the non-strict default keeps the historical replicate-silently rule
    pol.validate_for(cfg, _amesh(1, 64))


def test_scheduler_rejects_unserved_tier_at_submit():
    cfg = get_config("granite-8b", smoke=True)
    params = T.build_params(cfg, QuantMaker(jax.random.PRNGKey(0)))
    eng = ServingEngine(cfg, params, ServeConfig(
        max_len=32, n_slots=2, prefill_chunk=8))
    sched = Scheduler(eng)
    with pytest.raises(ValueError, match="no pool at that tier"):
        sched.submit(Request(prompt=np.arange(1, 5, dtype=np.int32),
                             kv_policy="int8"))


# ---------------------------------------------------------------------------
# Legacy-adapter bit-identity + deprecated-global removal
# ---------------------------------------------------------------------------
def _generate(engine, batch, max_new=5):
    return engine.generate(batch, max_new_tokens=max_new)["generated"]


def test_legacy_kv_dtype_adapter_bit_identical_single_device():
    """ServeConfig(kv_dtype='int8') and ServeConfig(policy=...) are the
    same engine: byte-identical greedy output (the dp=2 x tp=4 twin of
    this contract runs in the CI multi-device job below)."""
    cfg = get_config("granite-8b", smoke=True)
    params = T.build_params(cfg, QuantMaker(jax.random.PRNGKey(0)))
    batch = {"tokens": np.random.default_rng(11).integers(
        1, cfg.vocab, (3, 9)).astype(np.int32)}
    legacy = ServingEngine(cfg, params, ServeConfig(
        max_len=32, n_slots=4, prefill_chunk=8, kv_dtype="int8"))
    pol = ServingEngine(cfg, params, ServeConfig(
        max_len=32, n_slots=4, prefill_chunk=8,
        policy=PrecisionPolicy(kv="int8")))
    np.testing.assert_array_equal(_generate(legacy, batch),
                                  _generate(pol, batch))


def test_plan_adapter_bit_identical_to_policy_weights_under_mesh():
    """ServingEngine(plan=...) folds into the policy: same specs, same
    placement, same tokens as declaring the weights in the policy —
    exercised through the (1, 1)-mesh sharded code path."""
    cfg = get_config("granite-8b", smoke=True)
    plan = {"ffn.w_down": "bf16"}
    params = T.build_params(cfg, QuantMaker(jax.random.PRNGKey(0), plan=plan))
    batch = {"tokens": np.random.default_rng(12).integers(
        1, cfg.vocab, (2, 7)).astype(np.int32)}
    mesh = make_mesh((1, 1), ("data", "model"))
    via_plan = ServingEngine(cfg, params, ServeConfig(
        max_len=32, n_slots=2, prefill_chunk=8, mesh=mesh), plan=plan)
    via_policy = ServingEngine(cfg, params, ServeConfig(
        max_len=32, n_slots=2, prefill_chunk=8, mesh=mesh,
        policy=PrecisionPolicy(weights=tuple(plan.items()))))
    np.testing.assert_array_equal(_generate(via_plan, batch),
                                  _generate(via_policy, batch))
    # without either spelling the structure check still fires eagerly
    with pytest.raises(ValueError, match="plan"):
        ServingEngine(cfg, params, ServeConfig(
            max_len=32, n_slots=2, prefill_chunk=8, mesh=mesh))


def test_serve_path_has_no_deprecated_kernel_global_call_sites():
    """Acceptance guard: ``set_use_kernel`` / ``set_under_partitioning``
    survive only as deprecation shims — the serve/launch paths drive
    ``kernels.ops.declare_execution`` instead."""
    import inspect

    import repro.launch.steps as steps
    import repro.serve.engine as engine
    import repro.serve.scheduler as scheduler
    for mod in (engine, scheduler, steps):
        src = inspect.getsource(mod)
        assert "set_under_partitioning" not in src, mod.__name__
        assert "set_use_kernel" not in src, mod.__name__


# ---------------------------------------------------------------------------
# Runtime per-request tier switching (the acceptance contract)
# ---------------------------------------------------------------------------
def _run_tiered(engine, jobs, max_new=6, tiers=None, late_from=None):
    """Serve ``jobs`` = [(prompt, kv_policy or None, temperature)];
    requests from index ``late_from`` on are admitted mid-flight."""
    sched = Scheduler(engine, tiers=tiers)
    late_from = len(jobs) if late_from is None else late_from

    def mk(i):
        p, tier, temp = jobs[i]
        return Request(prompt=p, id=i, kv_policy=tier,
                       sampling=SamplingParams(temperature=temp,
                                               max_new_tokens=max_new))
    reqs = [sched.submit(mk(i)) for i in range(late_from)]
    while sched.n_decode_steps < 2:
        sched.step()
    reqs += [sched.submit(mk(i)) for i in range(late_from, len(jobs))]
    sched.run(max_steps=400)
    assert all(r.is_finished for r in reqs)
    return [list(r.output_tokens) for r in reqs], sched


def test_mixed_tier_engine_bit_identical_per_tier():
    """THE runtime-switching contract (DESIGN.md §12): one engine serves
    bf16-KV and int8-KV requests interleaved — mid-flight admission, a
    seeded temperature row included — and every request's output is
    bit-identical to a single-tier engine run at its precision."""
    cfg = get_config("granite-8b", smoke=True)
    params = T.build_params(cfg, QuantMaker(jax.random.PRNGKey(0)))
    rng = np.random.default_rng(13)
    prompts = [rng.integers(1, cfg.vocab, (n,)).astype(np.int32)
               for n in (9, 6, 11, 8)]
    temps = (0.0, 0.0, 0.7, 0.0)

    def single(tier):
        eng = ServingEngine(cfg, params, ServeConfig(
            max_len=32, n_slots=4, prefill_chunk=8, kv_dtype=tier))
        out, _ = _run_tiered(eng, [(p, None, t)
                                   for p, t in zip(prompts, temps)])
        return out

    ref = {t: single(t) for t in ("bf16", "int8")}

    eng = ServingEngine(cfg, params, ServeConfig(
        max_len=32, n_slots=4, prefill_chunk=8))
    tiers = ("bf16", "int8", "bf16", "int8")
    got, sched = _run_tiered(
        eng, [(p, t, tp) for p, t, tp in zip(prompts, tiers, temps)],
        tiers=["bf16", "int8"], late_from=3)
    assert got == [ref[t][i] for i, t in enumerate(tiers)]
    # the mixed run really ran both tiers concurrently from one engine
    rep = sched.metrics.report()
    assert rep["tiers"] == {"bf16": 4, "int8": 4}
    assert rep["n_requests"] == 4


def test_mixed_tier_decode_cohorts_one_dispatch_per_tier():
    """Decode rounds issue one dispatch per ACTIVE tier cohort: a round
    with both tiers decoding counts 2 dispatches; a single-tier workload
    on the same scheduler counts 1 per round."""
    cfg = get_config("granite-8b", smoke=True)
    params = T.build_params(cfg, QuantMaker(jax.random.PRNGKey(0)))
    eng = ServingEngine(cfg, params, ServeConfig(
        max_len=32, n_slots=2, prefill_chunk=8, max_burst=1))
    sched = Scheduler(eng, tiers=["bf16", "int8"])
    p = np.arange(1, 9, dtype=np.int32)
    for i, tier in enumerate(("bf16", "int8")):
        sched.submit(Request(prompt=p, id=i, kv_policy=tier,
                             sampling=SamplingParams(max_new_tokens=4)))
    sched.run(max_steps=100)
    # one-chunk prompts prefill on consecutive steps, then each request
    # decodes 3 tokens; the overlapping rounds dispatch once PER TIER:
    # 1 (bf16 alone) + 2 + 2 (both) + 1 (int8 alone) = 6 dispatches for
    # 6 decode token-steps — cohorts never share a dispatch across tiers
    assert sched.metrics.decode_token_steps == 6
    assert sched.metrics.decode_dispatches == 6


def test_budget_derived_tier_pools_capacity_ratio():
    """The capacity story: from ONE cache budget per tier, the int8 tier
    admits ~1.94x the bf16 slots at the paper models' d_head=128 (codes
    pack 4-per-word + one f32 scale per (position, head): 2D/(D+4))."""
    cfg = dataclasses.replace(get_config("granite-8b", smoke=True),
                              d_head=128)
    params = T.build_params(cfg, QuantMaker(jax.random.PRNGKey(0)))
    eng = ServingEngine(cfg, params, ServeConfig(
        max_len=32, prefill_chunk=8, cache_budget_bytes=1_000_000))
    sched = Scheduler(eng, tiers=["bf16", "int8"])
    slots = {t: p.n_slots for t, p in sched.pools.items()}
    assert slots["int8"] >= 1.9 * slots["bf16"], slots
    # and the pools really are that tier
    assert sched.pools["int8"].kv_dtype == "int8"
    assert sched.pools["bf16"].bytes_per_token > \
        1.9 * sched.pools["int8"].bytes_per_token


# ---------------------------------------------------------------------------
# Multi-device twins (CI multi-device job)
# ---------------------------------------------------------------------------
@multi_device
def test_legacy_kv_dtype_adapter_bit_identical_dp2_tp4():
    """The adapter bit-identity contract under dp=2 x tp=4: legacy
    kv_dtype spelling == policy spelling, byte for byte."""
    cfg = get_config("granite-8b", smoke=True)
    params = T.build_params(cfg, QuantMaker(jax.random.PRNGKey(0)))
    batch = {"tokens": np.random.default_rng(17).integers(
        1, cfg.vocab, (4, 9)).astype(np.int32)}

    def build(**kw):
        mesh = make_mesh((2, 4), ("data", "model"))
        return ServingEngine(cfg, params, ServeConfig(
            max_len=32, n_slots=8, prefill_chunk=8, mesh=mesh, **kw))

    legacy = _generate(build(kv_dtype="int8"), batch)
    pol = _generate(build(policy=PrecisionPolicy(kv="int8")), batch)
    np.testing.assert_array_equal(legacy, pol)
    # and both match the single-device engine.  Under the mesh, 'auto'
    # resolves to the pallas kernels (DESIGN.md §14); meshless it resolves
    # to jnp, a different numeric path (fused-f32 vs bf16 dequant) — so
    # the meshless reference pins the SAME resolved mode.  The mesh-vs-
    # meshless contract per mode is test_kernel_mesh_equivalence_matrix's.
    single = _generate(ServingEngine(cfg, params, ServeConfig(
        max_len=32, n_slots=8, prefill_chunk=8,
        policy=PrecisionPolicy(kv="int8", kernel="pallas"))), batch)
    np.testing.assert_array_equal(legacy, single)


@multi_device
def test_mixed_tier_engine_bit_identical_per_tier_dp2_tp4():
    """Runtime tier switching composed with sharded serving: one dp=2 x
    tp=4 engine, two tier pools, mid-flight admission — each tier's
    output bit-identical to the meshless single-tier engine."""
    cfg = get_config("granite-8b", smoke=True)
    params = T.build_params(cfg, QuantMaker(jax.random.PRNGKey(0)))
    rng = np.random.default_rng(19)
    prompts = [rng.integers(1, cfg.vocab, (n,)).astype(np.int32)
               for n in (9, 6, 11, 8)]
    jobs_ref = [(p, None, 0.0) for p in prompts]

    def single(tier):
        eng = ServingEngine(cfg, params, ServeConfig(
            max_len=32, n_slots=8, prefill_chunk=8, kv_dtype=tier))
        return _run_tiered(eng, jobs_ref)[0]

    ref = {t: single(t) for t in ("bf16", "int8")}
    mesh = make_mesh((2, 4), ("data", "model"))
    eng = ServingEngine(cfg, params, ServeConfig(
        max_len=32, n_slots=8, prefill_chunk=8, mesh=mesh))
    tiers = ("bf16", "int8", "int8", "bf16")
    got, _ = _run_tiered(eng,
                         [(p, t, 0.0) for p, t in zip(prompts, tiers)],
                         tiers=["bf16", "int8"], late_from=3)
    assert got == [ref[t][i] for i, t in enumerate(tiers)]
