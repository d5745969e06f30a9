"""Tensor-parallel serving tests (DESIGN.md §10).

Three tiers:
  * spec-level assertions run everywhere — they build PartitionSpec trees
    over an ``AbstractMesh`` (no devices needed);
  * single-device mesh tests run everywhere — a (1, 1) mesh exercises the
    whole mesh code path (device_put, explicit in/out shardings, donation)
    without multi-device XLA;
  * multi-device tests need >= 8 host devices and skip otherwise — CI's
    multi-device job sets ``XLA_FLAGS=--xla_force_host_platform_device_count=8``
    (so does ``launch/serve.py --force-host-devices 8``).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro.configs import get_config
from repro.launch.mesh import make_mesh
from repro.models.common import InitMaker, QLinear, QuantMaker
from repro.models import transformer as T
from repro.quant.schemes import get_scheme
from repro.runtime import partitioning as PT
from repro.serve import (Request, SamplingParams, ServeConfig, ServingEngine,
                         Scheduler)

multi_device = pytest.mark.skipif(
    len(jax.devices()) < 8,
    reason="needs XLA_FLAGS=--xla_force_host_platform_device_count=8")


@pytest.fixture(autouse=True)
def _reset_execution_record():
    """Engines with a multi-device mesh declare themselves into the global
    execution record (kernel mode, mesh, per-leaf weight specs); reset it —
    and the per-site fallback-warning registry — so nothing leaks into
    later test files."""
    yield
    from repro.kernels import ops
    ops.declare_execution(kernel="auto", mesh=None, weight_specs=None)
    ops.reset_site_warnings()


def _amesh(dp, tp):
    return AbstractMesh((dp, tp), ("data", "model"))


def _qlinear_spec_leaves(cfg, specs):
    """[(name-path, QLinear spec node)] for every quantized leaf."""
    out = []
    jax.tree_util.tree_map_with_path(
        lambda path, leaf: out.append((jax.tree_util.keystr(path), leaf))
        if isinstance(leaf, QLinear) else None,
        specs, is_leaf=lambda x: isinstance(x, (QLinear, P)))
    return out


# ---------------------------------------------------------------------------
# Spec-level: packed-word / scale-group K alignment (no devices needed)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("arch,scheme_name", [
    ("granite-8b", "awq_int4"),      # group 128
    ("starcoder2-15b", "mxfp4"),     # group 32
])
def test_param_specs_k_sharding_respects_words_and_scale_groups(
        arch, scheme_name):
    """On an 8-way model axis, a K-axis shard boundary of a packed
    quantized leaf must land on an int32 code-word boundary AND a
    scale-group boundary, and codes/scales must shard in lockstep."""
    cfg = get_config(arch)
    scheme = get_scheme(scheme_name)
    tp = 8
    mesh = _amesh(1, tp)
    specs = PT.param_specs(cfg, mesh, train=False, quantize=True)
    qleaves = _qlinear_spec_leaves(cfg, specs)
    assert qleaves, f"{arch}: expected quantized leaves"
    per_word = 32 // scheme.weight_bits
    n_k_sharded = 0
    for name_path, leaf in qleaves:
        # K axis = first dim after the layer stack
        nstack = len(leaf.packed) - 2
        pk, sk = leaf.packed[nstack], leaf.scales[nstack]
        assert pk == sk, (
            f"{name_path}: packed K-axis={pk!r} != scales K-axis={sk!r} "
            "(a shard must own the scale rows of its own K rows)")
        if pk != "model":
            continue
        n_k_sharded += 1
        k = leaf.shape[0]
        k_shard = k // tp
        assert k_shard % per_word == 0, \
            f"{name_path}: K shard {k_shard} splits an int32 code word"
        group = min(scheme.group_size, k)
        assert k_shard % group == 0, \
            f"{name_path}: K shard {k_shard} splits a scale group {group}"
    # the full-size configs genuinely exercise K sharding (wo / w_down)
    assert n_k_sharded > 0, f"{arch}: no K-sharded quantized leaf at tp={tp}"


def test_param_specs_blocks_k_shard_when_scale_groups_do_not_divide():
    """Smoke dims have single-group scales (K <= group): the K axis must
    stay replicated even though the packed word count divides the axis —
    previously this sharded codes against unsplittable scales."""
    cfg = get_config("granite-8b", smoke=True)
    specs = PT.param_specs(cfg, _amesh(1, 4), train=False, quantize=True)
    for name_path, leaf in _qlinear_spec_leaves(cfg, specs):
        nstack = len(leaf.packed) - 2
        assert leaf.packed[nstack] is None, \
            f"{name_path}: K sharded across a single scale group"


def test_param_specs_head_granularity_guard():
    """Attention projection head dims shard only when the head COUNT
    divides the model axis: granite has 32 q / 8 kv heads, so at tp=8 both
    shard, at tp=16 only q does — even though the raw dim h*dh divides 16
    in both cases (sub-head splits broke the [b,s,h,dh] reshape)."""
    cfg = get_config("granite-8b")   # 32 heads, 8 kv heads

    def axes(tp):
        specs = PT.param_specs(cfg, _amesh(1, tp), train=False, quantize=True)
        q = dict(_qlinear_spec_leaves(cfg, specs))
        wq = [v for k, v in q.items() if "wq" in k][0]
        wk = [v for k, v in q.items() if "wk" in k][0]
        return wq.packed[-1], wk.packed[-1]

    assert axes(8) == ("model", "model")
    assert axes(16) == ("model", None)   # 8 kv heads cannot split 16 ways


def test_serve_pool_pspec_axes_and_structure():
    """Pool specs: slots on 'data', heads on 'model' (iff divisible), the
    packed d_head dim NEVER sharded, scales tree mirrors the slab tree."""
    cfg = get_config("granite-8b")   # 8 kv heads
    mesh = _amesh(2, 4)
    spec = PT.serve_pool_pspec(cfg, mesh, 8, kv_dtype="int8")
    k_slab, v_slab = spec
    for slab in (k_slab, v_slab):
        # [L, slots, S, H, Dw] packed + [L, slots, S, H] scales
        assert slab.packed == P(None, "data", None, "model", None)
        assert slab.scales == P(None, "data", None, "model")
    # bf16 pool: plain specs, same axes
    spec = PT.serve_pool_pspec(cfg, mesh, 8, kv_dtype="bf16")
    assert spec[0] == P(None, "data", None, "model", None)
    # indivisible: 2 kv heads on a 4-way axis, 3 slots on a 2-way axis
    smoke = get_config("granite-8b", smoke=True)
    spec = PT.serve_pool_pspec(smoke, mesh, 3, kv_dtype="int8")
    assert spec[0].packed == P(None, None, None, None, None)
    with pytest.raises(ValueError):
        PT.serve_pool_pspec(get_config("xlstm-350m"), mesh, 8)


def test_bias_specs_follow_their_weights_output_axis():
    """starcoder2-15b's biases at tp=8: a bias shards like its weight's N
    axis (q's 48 heads and the FFN's 24,576 columns over 'model'; k and
    v's 4 heads, like their weights, replicated; o's and the FFN output's
    d_model, the weight's un-sharded N, replicated); LayerNorm betas stay
    whole."""
    cfg = get_config("starcoder2-15b")
    assert cfg.use_bias and cfg.norm == "layer"
    specs = PT.param_specs(cfg, _amesh(1, 8), train=False, quantize=True)
    attn, ffn = specs["layers"]["attn"], specs["layers"]["ffn"]
    for b, w in (("bq", "wq"), ("bk", "wk"), ("bv", "wv"), ("bo", "wo")):
        assert attn[b] == P(None, attn[w].packed[-1]), b
    for b, w in (("b_in", "w_in"), ("b_out", "w_out")):
        assert ffn[b] == P(None, ffn[w].packed[-1]), b
    assert attn["bq"] == ffn["b_in"] == P(None, "model")
    assert attn["bk"] == attn["bo"] == ffn["b_out"] == P(None, None)
    assert specs["layers"]["ln1"]["b"] == P(None, None)


def test_mla_pool_pspec_latent_stays_whole():
    """MLA pools shard slots only: the compressed latent is consumed whole
    by every head's absorbed contraction."""
    cfg = get_config("deepseek-v2-236b")
    spec = PT.serve_pool_pspec(cfg, _amesh(4, 2), 8, kv_dtype="bf16")
    assert spec == (P(None, "data", None, None), P(None, "data", None, None))


# ---------------------------------------------------------------------------
# QuantMaker plan override (satellite) + spec coherence
# ---------------------------------------------------------------------------
def test_quantmaker_plan_overrides_config_scheme():
    """A plan entry wins over the config scheme per leaf name: forcing
    ffn.w_down dense and attn.wq to mxfp4 changes exactly those leaves."""
    cfg = get_config("granite-8b", smoke=True)     # config: awq_int4
    plan = {"ffn.w_down": "bf16", "attn.wq": "mxfp4"}
    params = T.build_params(cfg, QuantMaker(jax.random.PRNGKey(0), plan=plan))
    layers = params["layers"]
    assert not isinstance(layers["ffn"]["w_down"], QLinear)   # forced dense
    assert layers["attn"]["wq"].scheme_name == "mxfp4"        # forced mxfp4
    assert layers["attn"]["wk"].scheme_name == "awq_int4"     # untouched
    # param_specs built with the same plan matches the tree leaf for leaf
    specs = PT.param_specs(cfg, _amesh(1, 4), train=False, quantize=True,
                           plan=plan)
    assert jax.tree_util.tree_structure(params) == \
        jax.tree_util.tree_structure(
            specs, is_leaf=lambda x: isinstance(x, P))
    # ... and without the plan it does NOT (the coherence failure the
    # engine guards against)
    specs_noplan = PT.param_specs(cfg, _amesh(1, 4), train=False,
                                  quantize=True)
    assert jax.tree_util.tree_structure(params) != \
        jax.tree_util.tree_structure(
            specs_noplan, is_leaf=lambda x: isinstance(x, P))


def test_quantmaker_draws_and_quantizes_one_layer_slice_at_a_time():
    """Stacked leaf slice l is the numpy quantizer applied to the slice's
    own draw, N(0, 1/K) from fold_in(leaf key, l); unstacked leaves draw
    from the leaf key itself."""
    from repro.quant.schemes import quantize_weights
    k, n, layers = 256, 64, 3
    leaf_key = jax.random.split(jax.random.PRNGKey(7))[1]   # InitMaker._next
    mk = QuantMaker(jax.random.PRNGKey(7))
    leaf = mk.dense("ffn.w_up", (layers,), k, n, scheme="awq_int4")
    for i in range(layers):
        w = np.asarray(jax.random.normal(jax.random.fold_in(leaf_key, i),
                                         (k, n), jnp.float32)) / np.sqrt(k)
        want = quantize_weights(get_scheme("awq_int4"), w)
        np.testing.assert_array_equal(np.asarray(leaf.packed[i]),
                                      np.asarray(want.packed))
        np.testing.assert_array_equal(np.asarray(leaf.scales[i]),
                                      np.asarray(want.scales))
    one = QuantMaker(jax.random.PRNGKey(7)).dense("attn.wq", (), k, n,
                                                  scheme="fp8")
    w = np.asarray(jax.random.normal(leaf_key, (k, n), jnp.float32)) \
        / np.sqrt(k)
    np.testing.assert_array_equal(
        np.asarray(one.packed),
        np.asarray(quantize_weights(get_scheme("fp8"), w).packed))


@pytest.mark.parametrize("arch", ["granite-8b", "qwen3-moe-30b-a3b"])
def test_quantmaker_builds_into_serving_shards(arch):
    """With the serving spec maker, every leaf is created in the sharding
    the engine expects and holds the same values as the meshless build —
    the engine's own placement is then a no-op."""
    cfg = get_config(arch, smoke=True)
    mesh = make_mesh((1, 1), ("data", "model"))
    plain = T.build_params(cfg, QuantMaker(jax.random.PRNGKey(0)))
    placed = T.build_params(cfg, QuantMaker(
        jax.random.PRNGKey(0),
        specs=PT.spec_maker(cfg, mesh, train=False)))
    want = PT.named(mesh, PT.param_specs(cfg, mesh, train=False))
    leaves = jax.tree_util.tree_leaves(placed)
    assert len(leaves) == len(jax.tree_util.tree_leaves(want))
    for a, b, sh in zip(jax.tree_util.tree_leaves(plain), leaves,
                        jax.tree_util.tree_leaves(want)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert b.sharding == sh, (b.sharding, sh)


def test_engine_rejects_plan_mismatch_under_mesh():
    cfg = get_config("granite-8b", smoke=True)
    plan = {"ffn.w_down": "bf16"}
    params = T.build_params(cfg, QuantMaker(jax.random.PRNGKey(0), plan=plan))
    mesh = make_mesh((1, 1), ("data", "model"))
    with pytest.raises(ValueError, match="plan"):
        ServingEngine(cfg, params, ServeConfig(max_len=32, mesh=mesh))
    # with the plan the engine builds (and the same params serve fine)
    ServingEngine(cfg, params, ServeConfig(max_len=32, mesh=mesh), plan=plan)


# ---------------------------------------------------------------------------
# Kernel fallback warnings: keyed by SITE, not latched per process (satellite)
# ---------------------------------------------------------------------------
def test_kernel_fallback_warns_once_per_site():
    """Fallback warnings are keyed by the call SITE (the weight leaf name):
    the first fallback at a site warns, repeats at the same site are silent
    — but a DIFFERENT site still gets its own warning instead of being
    consumed by the old per-process latch.  Every fallback still computes
    the same math on the jnp path."""
    import dataclasses as _dc
    import warnings as _warnings

    import jax.numpy as jnp
    from repro.kernels import ops
    from repro.quant.schemes import quantize_weights
    qw_a = quantize_weights(get_scheme("awq_int4"),
                            np.random.default_rng(0).normal(size=(64, 16)))
    qw_a = _dc.replace(qw_a, name="attn.wq")
    qw_b = _dc.replace(qw_a, name="ffn.w_up")
    x = jnp.asarray(np.random.default_rng(1).normal(size=(2, 64)),
                    jnp.bfloat16)
    ref = ops.quantized_matmul(x, qw_a, use_kernel=False)
    try:
        # legacy shim spelling: partitioned with no mesh — every kernel
        # site falls back (nothing to shard_map over), each warning once
        ops.declare_execution(kernel="pallas", partitioned=True)
        ops.reset_site_warnings()
        with pytest.warns(UserWarning, match="attn.wq"):
            out = ops.quantized_matmul(x, qw_a)
        np.testing.assert_array_equal(np.asarray(ref), np.asarray(out))
        # same site again: silent...
        with _warnings.catch_warnings():
            _warnings.simplefilter("error")
            out2 = ops.quantized_matmul(x, qw_a)
        # ...but still the jnp fallback, same math
        np.testing.assert_array_equal(np.asarray(ref), np.asarray(out2))
        # a different site was NOT consumed by the first warning
        with pytest.warns(UserWarning, match="ffn.w_up"):
            ops.quantized_matmul(x, qw_b)
        # explicit use_kernel=True bools keep the blanket downgrade: raw
        # kernel calls bypass the shard_map dispatch entirely
        with pytest.warns(UserWarning, match="explicit use_kernel"):
            out3 = ops.quantized_matmul(x, qw_a, use_kernel=True)
        np.testing.assert_array_equal(np.asarray(ref), np.asarray(out3))
        assert not ops.kernel_allowed(True)
    finally:
        ops.declare_execution(kernel="auto", mesh=None, weight_specs=None)
        ops.reset_site_warnings()


# ---------------------------------------------------------------------------
# Mesh engine: single-device path (runs in the tier-1 fast loop)
# ---------------------------------------------------------------------------
def test_mesh_engine_single_device_bit_identical():
    """A (1, 1) mesh walks the whole sharded code path — param placement,
    explicit in/out shardings, pool placement, donation — and must emit
    exactly the meshless tokens."""
    cfg = get_config("granite-8b", smoke=True)
    params = T.build_params(cfg, QuantMaker(jax.random.PRNGKey(0)))
    batch = {"tokens": np.random.default_rng(2).integers(
        1, cfg.vocab, (3, 9)).astype(np.int32)}
    base = ServingEngine(cfg, params, ServeConfig(
        max_len=32, n_slots=4, prefill_chunk=8, kv_dtype="int8"))
    ref = base.generate(batch, max_new_tokens=5)["generated"]
    mesh = make_mesh((1, 1), ("data", "model"))
    eng = ServingEngine(cfg, params, ServeConfig(
        max_len=32, n_slots=4, prefill_chunk=8, kv_dtype="int8", mesh=mesh))
    assert eng.topology == {"n_devices": 1, "dp": 1, "tp": 1}
    out = eng.generate(batch, max_new_tokens=5)["generated"]
    np.testing.assert_array_equal(ref, out)
    # pool really is placed with the serve-side shardings
    pool = eng.new_pool()
    assert pool.shardings is not None


def test_mesh_engine_single_device_bit_identical_with_biases():
    """The same on starcoder2's block: a (1, 1) mesh places the bias and
    LayerNorm beta leaves with their specs and emits the meshless
    tokens."""
    cfg = get_config("starcoder2-15b", smoke=True)
    params = T.build_params(cfg, QuantMaker(jax.random.PRNGKey(0)))
    batch = {"tokens": np.random.default_rng(2).integers(
        1, cfg.vocab, (3, 9)).astype(np.int32)}
    scfg = dict(max_len=32, n_slots=4, prefill_chunk=8)
    ref = ServingEngine(cfg, params, ServeConfig(**scfg)).generate(
        batch, max_new_tokens=5)["generated"]
    eng = ServingEngine(cfg, params, ServeConfig(
        **scfg, mesh=make_mesh((1, 1), ("data", "model"))))
    np.testing.assert_array_equal(
        ref, eng.generate(batch, max_new_tokens=5)["generated"])


# ---------------------------------------------------------------------------
# Multi-device: the acceptance contract (CI multi-device job)
# ---------------------------------------------------------------------------
def _run_workload(engine, prompts, max_new=6):
    """Scheduler run with the last request admitted mid-flight."""
    sched = Scheduler(engine)
    reqs = [sched.submit(Request(prompt=p,
                                 sampling=SamplingParams(max_new_tokens=max_new)))
            for p in prompts[:-1]]
    while sched.n_decode_steps < 2:
        sched.step()
    late = sched.submit(Request(
        prompt=prompts[-1], sampling=SamplingParams(max_new_tokens=max_new)))
    sched.run(max_steps=400)
    assert all(r.is_finished for r in reqs + [late])
    return [list(r.output_tokens) for r in reqs + [late]], sched


@multi_device
def test_dp2_tp4_bit_identical_greedy_with_mid_flight_admission():
    """THE sharded-serving contract: greedy output on a dp=2 x tp=4 mesh,
    quantized weights AND int8 KV pool, including a mid-flight admission,
    is bit-identical to the single-device run AT THE SAME KERNEL MODE
    (DESIGN.md §10, §14).  The default ``kernel='auto'`` resolves to
    pallas under the mesh, so its reference is the meshless run with
    pallas pinned — the mesh never changes the math; the kernel choice
    may (fused-f32 kernel vs bf16-dequant jnp, a bf16-rounding delta)."""
    from repro.quant.policy import PrecisionPolicy
    cfg = get_config("granite-8b", smoke=True)
    params = T.build_params(cfg, QuantMaker(jax.random.PRNGKey(0)))
    rng = np.random.default_rng(5)
    prompts = [rng.integers(1, cfg.vocab, (n,)).astype(np.int32)
               for n in (9, 6, 11, 8)]

    def engine(mesh, kernel="auto"):
        return ServingEngine(cfg, params, ServeConfig(
            max_len=32, n_slots=8, prefill_chunk=8,
            policy=PrecisionPolicy(kv="int8", kernel=kernel), mesh=mesh))

    ref, _ = _run_workload(engine(None, "pallas"), prompts)
    mesh = make_mesh((2, 4), ("data", "model"))
    got, sched = _run_workload(engine(mesh), prompts)   # auto -> pallas
    assert got == ref
    assert sched.metrics.report()["topology"] == \
        {"n_devices": 8, "dp": 2, "tp": 4}


@multi_device
def test_dp2_tp4_bit_identical_greedy_with_biases():
    """The contract on starcoder2's block (mxfp4 weights, LayerNorm with
    beta, a bias on every linear): the q and FFN-input biases shard over
    'model' with their weights' outputs, and greedy output on dp=2 x tp=4
    with a mid-flight admission equals the meshless run's."""
    from repro.quant.policy import PrecisionPolicy
    cfg = get_config("starcoder2-15b", smoke=True)
    params = T.build_params(cfg, QuantMaker(jax.random.PRNGKey(0)))
    rng = np.random.default_rng(5)
    prompts = [rng.integers(1, cfg.vocab, (n,)).astype(np.int32)
               for n in (9, 6, 11, 8)]

    def engine(mesh, kernel="auto"):
        return ServingEngine(cfg, params, ServeConfig(
            max_len=32, n_slots=8, prefill_chunk=8,
            policy=PrecisionPolicy(kernel=kernel), mesh=mesh))

    ref, _ = _run_workload(engine(None, "pallas"), prompts)
    got, _ = _run_workload(engine(make_mesh((2, 4), ("data", "model"))),
                           prompts)
    assert got == ref


@multi_device
def test_tp8_bit_identical_bf16_pool():
    """Pure model parallelism, plain bf16 pool: the jnp-path contract,
    kernel pinned on BOTH sides.  At tp=8 the smoke FFN (d_ff=128) shards
    8-way and GSPMD's split reduction drifts the logits by bf16 ulps vs
    the meshless single reduction — for either kernel mode (measured:
    ~0.017 max on jnp itself) — so token equality here is a property of
    this pinned workload, not of the mesh; it is pinned at the historical
    jnp trajectory.  Kernel-mode mesh equivalence lives in
    ``test_kernel_mesh_equivalence_matrix`` (dp2 x tp4, both modes)."""
    from repro.quant.policy import PrecisionPolicy
    cfg = get_config("granite-8b", smoke=True)
    params = T.build_params(cfg, InitMaker(jax.random.PRNGKey(0)))
    rng = np.random.default_rng(7)
    prompts = [rng.integers(1, cfg.vocab, (n,)).astype(np.int32)
               for n in (8, 10, 5)]

    def engine(mesh):
        return ServingEngine(cfg, params, ServeConfig(
            max_len=32, n_slots=4, prefill_chunk=8,
            policy=PrecisionPolicy(kernel="jnp"), mesh=mesh))

    ref, _ = _run_workload(engine(None), prompts)
    got, _ = _run_workload(
        engine(make_mesh((1, 8), ("data", "model"))), prompts)
    assert got == ref


@multi_device
def test_sharded_pool_placement_and_donation():
    """The pool cache is actually laid out per serve_pool_pspec (slots on
    'data'), and the decode step donates: the cache buffer is rebound, not
    copied (same sharding in and out)."""
    cfg = get_config("granite-8b", smoke=True)
    params = T.build_params(cfg, QuantMaker(jax.random.PRNGKey(0)))
    mesh = make_mesh((2, 4), ("data", "model"))
    eng = ServingEngine(cfg, params, ServeConfig(
        max_len=32, n_slots=8, prefill_chunk=8, kv_dtype="int8", mesh=mesh))
    pool = eng.new_pool()
    leaf = jax.tree_util.tree_leaves(pool.cache)[0]
    assert leaf.sharding.spec[1] == "data"          # slots axis sharded
    slot = pool.alloc()
    prompt = np.arange(1, 9, dtype=np.int32)
    eng.prefill_into_slots(pool, [slot], [prompt])
    before = jax.tree_util.tree_leaves(pool.cache)[0].sharding
    toks = np.zeros((8,), np.int32)
    sampled = eng.decode_slots(pool, toks)           # fused: ids, not logits
    assert sampled.shape == (8,) and sampled.dtype == np.int32
    after = jax.tree_util.tree_leaves(pool.cache)[0].sharding
    assert before == after                           # layout is pinned


# ---------------------------------------------------------------------------
# Kernel-under-mesh equivalence matrix (DESIGN.md §14, CI multi-device job)
# ---------------------------------------------------------------------------
@multi_device
@pytest.mark.parametrize("kv", ["bf16", "int8", "fp8"])
def test_kernel_mesh_equivalence_matrix(kv):
    """THE sharded-kernel contract: greedy decode on a dp=2 x tp=4 mesh
    with ``kernel='pallas'`` (shard_map'd Pallas decode attention AND the
    packed-weight matvec path) emits tokens bit-identical to the meshless
    pallas run, and ``kernel='jnp'`` on the same mesh bit-identical to the
    meshless jnp run — per KV tier over awq_int4 weights, with a
    mid-flight admission and K>1 decode bursts in the workload.  The mesh
    NEVER changes the math for either mode; pallas-vs-jnp is a
    bf16-rounding-level delta (fused-f32 kernel vs bf16-dequant fallback),
    so the two modes are pinned against their own meshless baselines."""
    from repro.quant.policy import PrecisionPolicy
    cfg = get_config("granite-8b", smoke=True)
    params = T.build_params(cfg, QuantMaker(jax.random.PRNGKey(0)))
    rng = np.random.default_rng(11)
    prompts = [rng.integers(1, cfg.vocab, (n,)).astype(np.int32)
               for n in (9, 6, 11, 8)]

    def run(mesh, kernel):
        eng = ServingEngine(cfg, params, ServeConfig(
            max_len=48, n_slots=8, prefill_chunk=8, max_burst=4,
            policy=PrecisionPolicy(kv=kv, kernel=kernel), mesh=mesh))
        return _run_workload(eng, prompts)

    ref_j, _ = run(None, "jnp")
    ref_p, _ = run(None, "pallas")
    mesh = make_mesh((2, 4), ("data", "model"))
    jn, _ = run(mesh, "jnp")
    pl, sched = run(mesh, "pallas")
    assert jn == ref_j
    assert pl == ref_p
    assert any(k > 1 for k in sched.metrics.burst_hist)   # bursts really ran


@multi_device
def test_pallas_policy_validates_and_serves_under_mesh():
    """The PR 3 eager rejection is gone end to end: a ``kernel='pallas'``
    policy validates against a dp2 x tp4 mesh and the engine serves with
    it (the acceptance criterion's smoke form of the matrix above)."""
    from repro.quant.policy import PrecisionPolicy
    cfg = get_config("granite-8b", smoke=True)
    params = T.build_params(cfg, QuantMaker(jax.random.PRNGKey(0)))
    mesh = make_mesh((2, 4), ("data", "model"))
    pol = PrecisionPolicy(kernel="pallas").validate_for(cfg, mesh)
    eng = ServingEngine(cfg, params, ServeConfig(
        max_len=32, n_slots=8, prefill_chunk=8, policy=pol, mesh=mesh))
    batch = {"tokens": np.random.default_rng(3).integers(
        1, cfg.vocab, (4, 9)).astype(np.int32)}
    base = ServingEngine(cfg, params, ServeConfig(
        max_len=32, n_slots=8, prefill_chunk=8,
        policy=PrecisionPolicy(kernel="pallas")))
    ref = base.generate(batch, max_new_tokens=5)["generated"]
    out = eng.generate(batch, max_new_tokens=5)["generated"]
    np.testing.assert_array_equal(ref, out)


# ---------------------------------------------------------------------------
# Sharded kernels vs their ref.py oracles (bitwise)
# ---------------------------------------------------------------------------
@multi_device
@pytest.mark.parametrize("kv_dtype", ["bf16", "int8", "fp8"])
def test_sharded_decode_attention_bitwise_vs_oracle(kv_dtype):
    """``sharded_gqa_decode_attention`` on dp2 x tp4 (slots on 'data', KV
    heads on 'model') is BITWISE equal to the meshless kernel and to the
    shard-decomposed oracle — no cross-shard collective exists to change
    the f32 association."""
    from repro.kernels import ref as KREF
    from repro.kernels.decode_attention import (gqa_decode_attention,
                                                sharded_gqa_decode_attention)
    from repro.quant.kv_cache import QuantizedKV
    from repro.quant.schemes import get_kv_scheme, kv_quantize

    rng = np.random.default_rng(23)
    b, sk, hk, rep, dh = 4, 64, 4, 2, 16
    q = jnp.asarray(rng.normal(size=(b, 1, hk * rep, dh)), jnp.bfloat16)
    kc = rng.normal(size=(b, sk, hk, dh)).astype(np.float32)
    vc = rng.normal(size=(b, sk, hk, dh)).astype(np.float32)
    kc *= np.exp(rng.normal(size=(b, sk, hk, 1)))
    lens = np.array([64, 17, 33, 48], np.int32)
    if kv_dtype == "bf16":
        k = jnp.asarray(kc, jnp.bfloat16)
        v = jnp.asarray(vc, jnp.bfloat16)
    else:
        scheme = get_kv_scheme(kv_dtype)
        k = QuantizedKV(*kv_quantize(scheme, jnp.asarray(kc)), kv_dtype)
        v = QuantizedKV(*kv_quantize(scheme, jnp.asarray(vc)), kv_dtype)

    mesh = make_mesh((2, 4), ("data", "model"))
    got = sharded_gqa_decode_attention(q, k, v, lens, mesh=mesh)
    meshless = gqa_decode_attention(q, k, v, lens, interpret=True)
    oracle = KREF.sharded_decode_attention_ref(q, k, v, lens, dp=2, tp=4)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(meshless))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(oracle))


@multi_device
@pytest.mark.parametrize("scheme_name", ["awq_int4", "mxfp4", "fp8"])
@pytest.mark.parametrize("m", [2, 16])   # gemv and matmul block plans
def test_sharded_packed_matmul_bitwise_vs_oracle(scheme_name, m):
    """The shard_map'd weight kernel (policy dispatch under a mesh) equals
    ``sharded_packed_matmul_ref`` for both shard decompositions: N on
    'model' (and the meshless kernel — the K loop is untouched) and K on
    'model' (psum over f32 partials, same left-to-right association as the
    oracle's shard sum).  Equal to the ``TILED_REF_ATOL`` contract: in
    interpret mode the shard-local tile shapes compile to their own XLA
    CPU programs, whose f32 sums may round differently."""
    import jax.numpy as jnp
    from repro.kernels import ops, ref as KREF
    from repro.quant.schemes import quantize_weights

    tp = 4
    k, n = 512, 256
    rng = np.random.default_rng(31)
    qw = quantize_weights(get_scheme(scheme_name),
                          rng.normal(size=(k, n)).astype(np.float32))
    import dataclasses as _dc
    qw = _dc.replace(qw, name="lin")
    x = jnp.asarray(rng.normal(size=(m, k)), jnp.bfloat16)
    mesh = make_mesh((2, tp), ("data", "model"))

    def mesh_out(k_ax, n_ax):
        specs = {"lin": {"packed": (k_ax, n_ax), "scales": (k_ax, n_ax)}}
        try:
            ops.declare_execution(kernel="pallas", mesh=mesh,
                                  weight_specs=specs)
            return np.asarray(ops.quantized_matmul(
                x, qw, out_dtype=jnp.float32))
        finally:
            ops.declare_execution(kernel="auto", mesh=None, weight_specs=None)

    bm, bn, bk = (m, 256, 1024) if m <= 8 else (128, 128, 512)
    # N sharded over 'model': bitwise == meshless kernel == tiled oracle
    got_n = mesh_out(None, "model")
    meshless = np.asarray(ops.quantized_matmul(
        x, qw, use_kernel=True, out_dtype=jnp.float32))
    oracle_n = np.asarray(KREF.sharded_packed_matmul_ref(
        x, qw, tp=tp, shard_dim=1, bm=bm, bn=bn, bk=bk))
    for want in (meshless, oracle_n):
        np.testing.assert_allclose(
            got_n, want, rtol=0,
            atol=KREF.TILED_REF_ATOL * float(np.abs(want).max()))

    # K sharded over 'model' (joint word/scale-group boundaries): psum
    # matches the oracle's left-to-right shard sum
    if qw.scales.shape[0] % tp == 0:     # K-shard legal (group divides)
        got_k = mesh_out("model", None)
        oracle_k = np.asarray(KREF.sharded_packed_matmul_ref(
            x, qw, tp=tp, shard_dim=0, bm=bm, bn=bn, bk=bk))
        np.testing.assert_allclose(
            got_k, oracle_k, rtol=0,
            atol=KREF.TILED_REF_ATOL * float(np.abs(oracle_k).max()))


@multi_device
def test_sharded_w8a8_matmul_bitwise_vs_meshless():
    """w8a8 under the mesh: activations quantize globally (per-tensor
    absmax) OUTSIDE shard_map, the int8 kernel N-shards — int32
    accumulation is exact, so sharded == meshless bitwise."""
    import dataclasses as _dc
    from repro.kernels import ops
    from repro.quant.schemes import quantize_weights

    rng = np.random.default_rng(37)
    qw = quantize_weights(get_scheme("w8a8"),
                          rng.normal(size=(256, 128)).astype(np.float32))
    qw = _dc.replace(qw, name="lin")
    x = jnp.asarray(rng.normal(size=(4, 256)), jnp.bfloat16)
    meshless = np.asarray(ops.quantized_matmul(
        x, qw, use_kernel=True, out_dtype=jnp.float32))
    mesh = make_mesh((2, 4), ("data", "model"))
    try:
        ops.declare_execution(kernel="pallas", mesh=mesh, weight_specs={
            "lin": {"packed": (None, "model"), "scales": (None, "model")}})
        got = np.asarray(ops.quantized_matmul(x, qw, out_dtype=jnp.float32))
    finally:
        ops.declare_execution(kernel="auto", mesh=None, weight_specs=None)
    np.testing.assert_array_equal(got, meshless)
