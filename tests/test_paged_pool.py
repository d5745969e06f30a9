"""Paged KV pool (DESIGN.md §15): bit-identity vs the slab pool, prefix
cache / copy-on-write semantics, page-granular budget accounting, and the
paged-attention oracle pin.

The pinned contract: a ``PagedKVPool`` scheduler produces EXACTLY the
tokens the slab-pool scheduler produces — greedy and seeded temperature,
single-device and dp2 x tp4, mid-flight admission, prefix hit and prefix
miss.  The sharded tests need >= 8 host devices (CI's multi-device job
sets ``XLA_FLAGS=--xla_force_host_platform_device_count=8``) and skip
otherwise.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.launch.mesh import make_mesh
from repro.models.common import InitMaker
from repro.models import transformer as T
from repro.serve import (PageAllocator, Request, SamplingParams, ServeConfig,
                         ServingEngine, Scheduler, bytes_per_page,
                         pages_for_budget)

multi_device = pytest.mark.skipif(
    len(jax.devices()) < 8,
    reason="needs XLA_FLAGS=--xla_force_host_platform_device_count=8")


@pytest.fixture(scope="module")
def cfg():
    return get_config("granite-8b", smoke=True)


@pytest.fixture(scope="module")
def params(cfg):
    return T.build_params(cfg, InitMaker(jax.random.PRNGKey(0)))


@pytest.fixture(scope="module")
def engines(cfg, params):
    """(slab, paged) engine pair over identical weights and serve knobs."""
    slab = ServingEngine(cfg, params, ServeConfig(
        max_len=48, n_slots=4, prefill_chunk=8))
    paged = ServingEngine(cfg, params, ServeConfig(
        max_len=48, n_slots=4, prefill_chunk=8, paged=True))
    return slab, paged


def _prompts(cfg, lens, seed=3):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, cfg.vocab, (n,)).astype(np.int32) for n in lens]


def _serve(engine, prompts, *, max_new=5, temperature=0.0, seed=0,
           pool=None, max_steps=300):
    sched = Scheduler(engine, pool=pool)
    reqs = [sched.submit(Request(prompt=p, sampling=SamplingParams(
        max_new_tokens=max_new, temperature=temperature, seed=seed)))
        for p in prompts]
    sched.run(max_steps=max_steps)
    return [np.asarray(r.output_tokens) for r in reqs], sched, reqs


# ---------------------------------------------------------------------------
# Scheduler equivalence: paged == slab, token for token
# ---------------------------------------------------------------------------
def test_paged_bit_identical_greedy(cfg, engines):
    """Greedy paged output == slab output on mixed prompt lengths (page-
    aligned, ragged, and below one page)."""
    slab, paged = engines
    prompts = _prompts(cfg, [8, 6, 10])
    want, _, _ = _serve(slab, prompts)
    got, sched, _ = _serve(paged, prompts)
    for w, g in zip(want, got):
        np.testing.assert_array_equal(w, g)
    sched.pool.allocator.check()


def test_paged_bit_identical_seeded_temperature(cfg, engines):
    """Seeded temperature sampling (bursts included) is bit-identical —
    the per-(request, step) key schedule is independent of the pool
    layout."""
    slab, paged = engines
    prompts = _prompts(cfg, [8, 9, 16], seed=7)
    want, _, _ = _serve(slab, prompts, temperature=0.8, seed=11)
    got, sched, _ = _serve(paged, prompts, temperature=0.8, seed=11)
    for w, g in zip(want, got):
        np.testing.assert_array_equal(w, g)
    sched.pool.allocator.check()


def test_paged_mid_flight_admission(cfg, engines):
    """A request admitted while others decode gets its solo tokens — page
    allocation for the newcomer cannot perturb in-flight rows."""
    slab, paged = engines
    prompts = _prompts(cfg, [8, 6, 10], seed=4)
    solo = [_serve(slab, [p])[0][0] for p in prompts]

    sched = Scheduler(paged)
    first = [sched.submit(Request(prompt=p,
                                  sampling=SamplingParams(max_new_tokens=5)))
             for p in prompts[:2]]
    while sched.n_decode_steps < 2:
        sched.step()
    assert any(r.n_generated > 0 for r in first)
    late = sched.submit(Request(prompt=prompts[2],
                                sampling=SamplingParams(max_new_tokens=5)))
    sched.run(max_steps=300)
    for req, want in zip(first + [late], solo):
        np.testing.assert_array_equal(np.asarray(req.output_tokens), want)
    sched.pool.allocator.check()


def test_prefix_hit_bit_identical_and_skips_prefill(cfg, engines):
    """Resubmitting served prompts into the same pool adopts their cached
    prefix pages: whole-page prefixes are skipped (full-cover hits re-run
    only the final chunk) and the continuation is bit-identical."""
    slab, paged = engines
    prompts = _prompts(cfg, [8, 6, 10])          # page size == chunk == 8
    want, _, _ = _serve(slab, prompts)
    _, sched, _ = _serve(paged, prompts)         # populates the prefix cache
    pool = sched.pool
    got, sched2, reqs = _serve(paged, prompts, pool=pool)
    for w, g in zip(want, got):
        np.testing.assert_array_equal(w, g)
    # 8-token prompt: full-cover hit (one whole page); 6-token prompt:
    # below one page, miss; 10-token prompt: first page hit, tail re-run
    assert [r.prefix_hit_tokens for r in reqs] == [8, 0, 8]
    # the hit requests resumed prefill past the adopted pages
    rep = sched2.metrics.report()
    assert rep["prefix_hits"] == 2 and rep["prefix_misses"] == 1
    assert rep["prefix_hit_tokens"] == 16
    pool.allocator.check()


def test_paged_int8_tier_bit_identical(cfg, params):
    """Quantized KV pages (packed codes + scales gathered in lockstep)
    keep the paged == slab contract."""
    slab = ServingEngine(cfg, params, ServeConfig(
        max_len=48, n_slots=4, prefill_chunk=8, kv_dtype="int8"))
    paged = ServingEngine(cfg, params, ServeConfig(
        max_len=48, n_slots=4, prefill_chunk=8, kv_dtype="int8", paged=True))
    prompts = _prompts(cfg, [9, 16, 8], seed=5)
    want, _, _ = _serve(slab, prompts)
    got, sched, _ = _serve(paged, prompts)
    for w, g in zip(want, got):
        np.testing.assert_array_equal(w, g)
    sched.pool.allocator.check()


def test_paged_small_arena_queues_and_drains(cfg, params):
    """An arena too small for every request at once admits on *pages
    available*: overflow requests wait, are admitted as retirements free
    pages (evicting cache-only pages under pressure), and still produce
    their solo tokens."""
    paged = ServingEngine(cfg, params, ServeConfig(
        max_len=48, n_slots=4, prefill_chunk=8, paged=True))
    prompts = _prompts(cfg, [8, 8, 10, 9], seed=9)
    solo = [_serve(paged, [p])[0][0] for p in prompts]
    # minimum legal arena: garbage page + one full 6-page slot.  Each
    # request needs 2 pages (prompt + max_new 5 <= 16 positions), so only
    # three of four fit at once — the fourth queues on pages, not slots.
    from repro.serve import PagedKVPool
    pool = PagedKVPool(cfg, 4, 48, align=8, page_size=8, n_pages=7)
    sched = Scheduler(paged, pool=pool)
    reqs = [sched.submit(Request(prompt=p,
                                 sampling=SamplingParams(max_new_tokens=5)))
            for p in prompts]
    queued = False
    for _ in range(300):
        if all(r.is_finished for r in reqs):
            break
        sched.step()
        queued = queued or any(not r.is_finished and r.slot is None
                               for r in reqs)
    assert queued, "arena of 7 pages should not admit 4 x 2-page requests"
    assert len(sched.finished) == 4
    for req, want in zip(reqs, solo):
        np.testing.assert_array_equal(np.asarray(req.output_tokens), want)
    # retired prompts stay behind as cache-only pages
    assert pool.allocator.pages_cached >= 1
    pool.allocator.check()


# ---------------------------------------------------------------------------
# Sharded serving (dp2 x tp4): pages ride the slot axis
# ---------------------------------------------------------------------------
@multi_device
def test_paged_bit_identical_dp2_tp4(cfg, params):
    """Paged == slab under a 2x4 mesh, greedy and seeded temperature —
    the page arena shards where the slab's slot axis did and the table
    rides the data axis, so GSPMD's gather/scatter reassembles exactly
    the meshless bytes."""
    mesh = make_mesh((2, 4), ("data", "model"))
    slab = ServingEngine(cfg, params, ServeConfig(
        max_len=48, n_slots=4, prefill_chunk=8, mesh=mesh))
    paged = ServingEngine(cfg, params, ServeConfig(
        max_len=48, n_slots=4, prefill_chunk=8, mesh=mesh, paged=True))
    prompts = _prompts(cfg, [8, 6, 10, 8])
    for temp in (0.0, 0.7):
        want, _, _ = _serve(slab, prompts, max_new=6, temperature=temp,
                            seed=11)
        got, sched, _ = _serve(paged, prompts, max_new=6, temperature=temp,
                               seed=11)
        for w, g in zip(want, got):
            np.testing.assert_array_equal(w, g)
        sched.pool.allocator.check()


# ---------------------------------------------------------------------------
# The paged-attention oracle (kernels/ref.py)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
def test_paged_oracle_pins_kernel_on_gathered_slab(kv_dtype):
    """Interpret-mode decode kernel fed the gathered virtual slab ==
    ``paged_decode_attention_ref`` on (arena, table), bit for bit — the
    §15 contract 'paged attention = page gather + slab attention' at the
    kernel level, quantized pages included."""
    from repro.kernels.decode_attention import gqa_decode_attention
    from repro.kernels.ref import paged_decode_attention_ref
    from repro.quant.kv_cache import QuantizedKV, gather_pages
    from repro.quant.schemes import get_kv_scheme, kv_quantize

    b, pp, ps, hk, dh = 3, 4, 8, 2, 16
    n_pages = 1 + b * pp
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(b, 1, 4, dh)), jnp.bfloat16)
    k = jnp.asarray(rng.normal(size=(n_pages, ps, hk, dh)), jnp.bfloat16)
    v = jnp.asarray(rng.normal(size=(n_pages, ps, hk, dh)), jnp.bfloat16)
    if kv_dtype != "bf16":
        def _q(x):
            packed, scales = kv_quantize(get_kv_scheme(kv_dtype), x)
            return QuantizedKV(packed, scales, kv_dtype)
        k, v = _q(k), _q(v)
    # ragged tables: unmapped (0) tail entries gather the garbage page
    table = np.zeros((b, pp), np.int32)
    table[0, :2] = [1, 2]
    table[1, :4] = [3, 2, 4, 5]       # page 2 shared between rows 0 and 1
    table[2, :1] = [6]
    lens = jnp.asarray([9, 25, 3], jnp.int32)
    tbl = jnp.asarray(table)

    want = paged_decode_attention_ref(q, k, v, tbl, lens)
    got = gqa_decode_attention(q, gather_pages(k, tbl), gather_pages(v, tbl),
                               lens, interpret=True)
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))


# ---------------------------------------------------------------------------
# Allocator semantics (unit level; random sequences in
# tests/test_paged_properties.py)
# ---------------------------------------------------------------------------
def test_allocator_cow_and_eviction_lifecycle():
    """Admission miss -> register -> full-cover hit COWs the write page;
    freeing drops refs; cache-only pages evict LRU under pressure."""
    a = PageAllocator(n_pages=9, page_size=8, n_slots=4, pages_per_slot=2,
                      align=8)
    p1 = list(range(100, 108))
    slot, pos, hit, copies = a.admit(p1, 4)
    assert (pos, hit, copies) == (0, 0, [])
    a.ensure(slot, 8, 9)              # decode write window
    a.register_prefix(slot, p1)
    a.check()
    # full-cover hit: prefill resumes at the final chunk, whose adopted
    # shared page is COW'd at admission
    slot2, pos2, hit2, copies2 = a.admit(p1, 4)
    assert (pos2, hit2) == (0, 8) and len(copies2) == 1
    src, dst = copies2[0]
    assert int(a.table[slot2, 0]) == dst and dst != src
    a.check()
    a.free_slot(slot), a.free_slot(slot2)
    a.check()
    # the registered page survives retirement as cache-only / evictable
    assert a.pages_cached == 1 and a.n_free_slots == 4
    # arena pressure evicts it: materialize all 8 usable pages for fresh
    # prompts (allocation is lazy — only a real _alloc_page evicts)
    slots = []
    for i in range(4):
        s, _, h, _ = a.admit([1000 + 16 * i + j for j in range(16)], 0)
        assert h == 0
        slots.append(s)
    for s in slots:
        a.ensure(s, 8, 16)            # second page of each slot
    assert a.pages_cached == 0 and a.n_evictions == 1
    a.check()


def test_allocator_double_free_and_exhaustion():
    a = PageAllocator(n_pages=5, page_size=8, n_slots=2, pages_per_slot=2,
                      align=8)
    r = a.admit(list(range(16)), 0)
    assert r is not None
    # second 2-page request doesn't fit 4 usable pages minus 2 held
    assert a.admit(list(range(50, 66)), 0) is not None
    assert a.admit(list(range(70, 86)), 0) is None    # slots and pages spent
    a.free_slot(r[0])
    with pytest.raises(AssertionError):
        a.free_slot(r[0])


def test_pages_for_budget_math(cfg):
    """Budget -> page count is exact division by bytes/page, with a floor
    of garbage + one worst-case request."""
    per = bytes_per_page(cfg, 8, kv_dtype="bf16")
    n = pages_for_budget(cfg, 48, per * 10 + per // 2, kv_dtype="bf16",
                         page_size=8)
    assert n == 10
    with pytest.raises(ValueError):
        # 48 positions -> 6 pages/slot; floor is 7 pages
        pages_for_budget(cfg, 48, per * 6, kv_dtype="bf16", page_size=8)


def test_paged_pool_accounting(cfg, engines):
    """Arena accounting: full provisioning matches slab capacity + the
    garbage page; bytes_per_token is position-granular."""
    _, paged = engines
    pool = paged.new_pool()
    assert pool.paged and pool.page_size == 8
    assert pool.n_pages == 1 + pool.n_slots * pool.pages_per_slot
    assert pool.capacity == 48 and pool.pages_per_slot == 6
    assert pool.pages_free == pool.n_pages - 1
    assert pool.bytes_per_token * pool.n_pages * pool.page_size \
        <= pool.cache_bytes


@pytest.mark.parametrize("case", ["admit", "ensure", "ensure_decode",
                                  "register_prefix", "double_free"])
def test_slab_pool_write_path(engines, case):
    """The slab's side of the one pool interface the scheduler drives:
    admission hands out the lowest free slot with prefill from 0 and no
    prefix hit, pinning and prefix publication touch nothing on the
    device, the steps take no trailing operand, and a double free still
    raises."""
    slab, _ = engines
    pool = slab.new_pool()
    cache = pool.cache
    assert pool.admit([5, 6, 7], 4) == (0, 0, 0)
    assert pool.admit([5, 6, 7], 4) == (1, 0, 0)
    if case == "admit":
        pool.free(0)
        assert pool.admit([9], 1) == (0, 0, 0)   # lowest free id, reused
        assert pool.admit([9], 1) == (2, 0, 0)
        assert pool.admit([9], 1) == (3, 0, 0)
        assert pool.admit([9], 1) is None        # every slot checked out
    elif case == "ensure":
        pool.lengths[0] = 5
        pool.ensure(0, 5 + 8)
        assert pool.lengths[0] == 5
    elif case == "ensure_decode":
        pool.ensure_decode([0, 1], 4, [4, 2])
    elif case == "register_prefix":
        assert pool.register_prefix(0, [5, 6, 7]) == 0
    else:
        pool.free(1)
        with pytest.raises(AssertionError):
            pool.free(1)
    assert pool.cache is cache                   # no device op ran
    assert pool.step_operands() == () and pool.step_operands(0) == ()


@pytest.mark.parametrize("layout", ["slab", "paged"])
def test_decode_logits_step_bit_identical(cfg, engines, layout):
    """``decode_slots_with_logits`` after the same prefill returns the
    slab's logits bit for bit on either layout, and on each layout its
    greedy pick is the fused decode step's token."""
    def logits_after_prefill(engine):
        pool = engine.new_pool()
        prompts = _prompts(cfg, [8, 6, 10, 13], seed=12)
        slots = [pool.admit(p, 4)[0] for p in prompts]
        engine.prefill_into_slots(pool, slots, prompts)
        tokens = np.zeros((pool.n_slots,), np.int32)
        tokens[slots] = [p[-1] for p in prompts]
        pool.ensure_decode(slots, 1)
        logits = np.asarray(engine.decode_slots_with_logits(pool, tokens))
        # lengths were not committed: the fused step rewrites the same rows
        return logits, engine.decode_slots(pool, tokens)

    want, _ = logits_after_prefill(engines[0])
    got, toks = logits_after_prefill(engines[layout == "paged"])
    assert np.isfinite(got).all()
    np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(got.argmax(-1), toks)
