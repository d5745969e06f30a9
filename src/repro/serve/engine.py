"""Mixed-precision serving engine: step-level primitives over a KV pool.

This is the system-level consumer of the paper's technique: checkpoint
weights are stored in the per-layer mixed-precision plan (projections /
experts in INT4/FP8/FP4/INT8 packed codes -> the XtraMAC-style MACs;
attention in BF16), and the engine exposes jitted steps over a
persistent cache — the per-tile "datatype control signal" of the paper's
GEMV engine becomes the static per-layer scheme in the compiled program
(DESIGN.md §2: JAX traces static dtypes, so runtime switching is realized
at layer granularity, which is the granularity the paper's own workloads
switch at).

Step primitives (DESIGN.md §7, §11):
  * ``prefill_chunk_into_slot`` — write one fixed-size chunk of one
    request's prompt into its KV pool slot (compiles once; prompts of any
    length are a host-side loop of chunks over a once-padded prompt).
  * ``prefill_into_slots``     — convenience loop of the above over whole
    prompts; returns last-true-position logits per request.
  * ``decode_slots``           — one decode step for ALL pool slots at
    once, each row writing/attending at its own length (per-row
    ``cache_index``), with sampling FUSED into the jit: per-slot keys and
    temperatures go in, only [n_slots] int32 token ids come out — the
    [n_slots, vocab] logits never leave the device.  Inactive slots ride
    along and are masked host-side; their garbage write lands exactly
    where the slot's next real write goes, so it is always overwritten
    before it could be attended.  (``decode_slots_with_logits`` keeps the
    logits-returning variant for score / first-token / diagnostic paths.)
  * ``decode_burst``           — K consecutive decode steps as ONE jitted
    ``lax.scan``: cache (donated), tokens, lengths and per-slot stop masks
    are threaded through the scan carry, a precomputed [K, n_slots, 2] key
    schedule rides the scan xs, and rows that retire mid-burst (EOS /
    max-new-tokens / capacity) freeze in place.  One dispatch and ONE host
    sync amortize over K generated tokens (DESIGN.md §11) — the software
    analogue of the paper's II=1 pipeline: the decode loop streams without
    per-token host intervention.

Both the continuous-batching ``Scheduler`` and the one-shot ``generate()``
(kept as a thin wrapper: it submits every row to a private scheduler and
drains it) drive these same primitives, so the two paths cannot drift —
greedy one-shot output IS scheduler output by construction.  Families
without a sliceable KV cache (ssm / hybrid / audio / vlm) keep the legacy
static-batch loop.

**Sharded serving** (DESIGN.md §10): with ``ServeConfig.mesh`` set to a
``dp x tp`` device mesh (axes 'data' x 'model'), the pool step primitives
become mesh-aware jits with explicit in/out shardings — params via
``partitioning.param_specs`` (packed code words and group scales shard
along N on the model axis; K only where the split lands on word AND
scale-group boundaries), the pool cache via
``partitioning.serve_pool_pspec`` (slots on 'data', KV heads on 'model').
The scheduler stays host-side and byte-identical: it sees the same
admit/free/lengths interface whether the pool under it lives on one chip
or thirty-two.  Buffer donation survives because the cache's in- and
out-shardings are pinned equal.

**Precision policy / runtime tiers** (DESIGN.md §12): the engine's whole
precision configuration is ONE ``quant.policy.PrecisionPolicy``
(``ServeConfig(policy=...)``; legacy ``kv_dtype=`` / ``plan=`` are thin
adapters emitting the equivalent policy, bit-identity pinned) — weight
schemes resolve the param shardings, ``policy.kv`` is the default KV
tier, ``policy.kernel`` drives kernel dispatch via
``kernels.ops.declare_execution``.  Every step primitive takes the pool
it operates on, and ``new_pool(kv_dtype=...)`` builds pools at any tier,
so one engine serves bf16/fp8/int8-KV traffic concurrently: compiled
steps are cached per ``(n_slots, capacity, tier)`` and the scheduler
cohorts decode batches per tier — the software analogue of XtraMAC's
runtime datatype switch.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.models import transformer as T
from repro.models.common import QLinear
from repro.obs.span import span
from repro.quant.policy import PrecisionPolicy, validate_kv_tier
from repro.runtime.fault_tolerance import StepFault

from .kv_pool import (KVCachePool, PagedKVPool, POOLABLE_FAMILIES,
                      pages_for_budget, slots_for_budget)
from .sampling import sample_rows


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    max_len: int = 512        # per-slot KV capacity (prompt + new tokens)
    temperature: float = 0.0
    eos_id: int = -1          # -1: never stop early
    # LEGACY adapter for the pool storage dtype: 'bf16' (or jnp.bfloat16)
    # for plain slabs, 'int8' / 'fp8' for quantized packed-codes + scales
    # slabs (DESIGN.md §9).  The canonical spelling is ``policy=``; giving
    # kv_dtype emits the equivalent policy (bit-identity pinned), and
    # after construction this field always reads the policy's canonical
    # tier name.  Unknown names — and raw dtypes no tier can honor —
    # raise HERE, not at first pool build.
    kv_dtype: Any = None
    n_slots: int = 8          # KV pool width = decode batch (static shape)
    prefill_chunk: int = 16   # chunked-prefill granularity (static shape)
    # upper bound on the decode-burst length K (DESIGN.md §11): the
    # scheduler plans K per round (clamped to 1 while admission or a
    # prefill is pending) and rounds it down to a power of two, so at most
    # log2(max_burst) burst variants ever compile.  1 disables bursts.
    max_burst: int = 8
    # optional cache-memory budget: when set, ``new_pool()`` derives the
    # slot count from KV bytes/token at the pool's tier instead of taking
    # ``n_slots`` — the knob that turns cache quantization into served
    # concurrency
    cache_budget_bytes: Optional[int] = None
    # paged KV pool (DESIGN.md §15): ``new_pool()`` builds a PagedKVPool —
    # per-slot page tables over a shared refcounted page arena with
    # copy-on-write prefix sharing — instead of the fixed slab.  Output is
    # bit-identical; capacity accounting becomes page-granular.
    paged: bool = False
    # arena page size in cache positions; 0 = prefill_chunk (pages are
    # chunk-aligned by construction — any explicit value must be a
    # multiple of prefill_chunk)
    page_size: int = 0
    # optional jax.sharding.Mesh ('data' x 'model' axes): shard params and
    # the KV pool across it (DESIGN.md §10).  None = single-device jits.
    mesh: Any = None
    # the unified precision contract (DESIGN.md §12): weight schemes, the
    # default KV tier and kernel dispatch as ONE declarative object.  None
    # derives a policy from the legacy knobs above.
    policy: Optional[PrecisionPolicy] = None
    # fault-injection hook (DESIGN.md §16): a callable
    # ``(kind, seq) -> Optional[str]`` consulted once per engine dispatch
    # (kind in {'prefill', 'decode', 'burst', 'verify'}; ``seq`` is the
    # monotone dispatch counter, so a test or bench can kill step #7
    # deterministically).  Return None for no fault; 'nan' to poison the
    # dispatch's sampled tokens (exercises the scheduler's poisoned-output
    # detector); any other string to raise ``StepFault(tag)`` in place of
    # the dispatch (lost shard / failed launch).  None disables the hook
    # at zero cost.
    fault_injector: Any = None
    # bounded retry: how many step faults one request may survive (each
    # costs a preempt-and-requeue with exponential backoff) before the
    # scheduler retires it with finish_reason='fault'
    max_fault_retries: int = 3

    def __post_init__(self):
        pol = self.policy
        if isinstance(pol, dict):
            pol = PrecisionPolicy.from_dict(pol)
        if pol is None:
            # legacy adapter: kv_dtype -> the equivalent policy.  Eager:
            # an unknown tier name raises at ServeConfig construction.
            pol = PrecisionPolicy.from_legacy(kv_dtype=self.kv_dtype)
        elif self.kv_dtype is not None \
                and validate_kv_tier(self.kv_dtype) != pol.kv:
            raise ValueError(
                f"ServeConfig: kv_dtype={self.kv_dtype!r} contradicts "
                f"policy.kv={pol.kv!r} — drop kv_dtype (the policy is "
                "the single source of truth)")
        object.__setattr__(self, "policy", pol)
        object.__setattr__(self, "kv_dtype", pol.kv)


def _has_qlinear(params) -> bool:
    """Whether the parameter tree carries packed quantized leaves (decides
    the ``quantize=`` parameterization of the matching spec tree)."""
    found = []
    jax.tree_util.tree_map(
        lambda x: found.append(isinstance(x, QLinear)), params,
        is_leaf=lambda x: isinstance(x, QLinear))
    return any(found)


# Families served through the slot pool / scheduler; VLM is poolable but its
# per-request patch inputs are not threaded through Request yet.
SCHEDULABLE_FAMILIES = ("dense", "moe")


class ServingEngine:
    def __init__(self, cfg: T.ModelConfig, params, serve_cfg: ServeConfig, *,
                 plan: Optional[Dict[str, str]] = None):
        """``plan``: LEGACY adapter for the per-name scheme overrides the
        checkpoint was built with (QuantMaker plan) — folded into the
        serve config's ``PrecisionPolicy`` as exact-name patterns, so the
        sharding spec tree matches the parameter tree leaf for leaf.  The
        canonical spelling is ``ServeConfig(policy=...)``."""
        self.cfg = cfg
        self.scfg = serve_cfg
        self.mesh = serve_cfg.mesh
        # the engine's effective precision contract: serve-config policy
        # with any legacy plan folded in, validated EAGERLY against the
        # model config and mesh (unknown schemes, group/K mismatches,
        # quantized-KV-on-MLA all raise here — not at first pool build or
        # first trace)
        self.policy = serve_cfg.policy.with_plan(plan or {}) \
            .validate_for(cfg, self.mesh)
        self._plan = self.policy.resolved_plan(cfg)
        self._param_shardings = None
        self._sharded_steps: Dict = {}   # (n_slots, capacity, tier) -> jits
        # monotone dispatch counter consulted by the fault-injection hook
        # (DESIGN.md §16) — advances only when an injector is armed, so
        # the disabled path costs nothing and dispatch numbering is
        # deterministic for a given workload
        self._fault_seq = 0

        # The execution policy (kernel mode + mesh + per-leaf kernel
        # sharding specs) is declared before every step call (not just
        # here) so lazily-traced jits always see THIS engine's kernel mode
        # and mesh, regardless of what other engines were constructed in
        # between.  Under a multi-device mesh the Pallas kernels run
        # shard_map'd over it (DESIGN.md §14) — the weight-spec map tells
        # the dispatch where each packed leaf's codes and scales live.
        self._partitioned = self.mesh is not None and self.mesh.size > 1
        self._kernel_weight_specs = None
        if self.mesh is not None:
            from repro.runtime import partitioning as PT
            if self._partitioned and _has_qlinear(params):
                self._kernel_weight_specs = PT.serve_weight_kernel_specs(
                    cfg, self.mesh, plan=self._plan)
            self._declare_execution()
            pspec = PT.param_specs(cfg, self.mesh, train=False,
                                   quantize=_has_qlinear(params),
                                   plan=self._plan)
            if jax.tree_util.tree_structure(params) != \
                    jax.tree_util.tree_structure(
                        pspec, is_leaf=lambda x: isinstance(x, P)):
                raise ValueError(
                    "parameter tree does not match its sharding spec tree — "
                    "params built with a QuantMaker plan must pass the same "
                    "plan to ServingEngine(..., plan=...) or declare it in "
                    "ServeConfig(policy=...) weight patterns")
            self._param_shardings = PT.named(self.mesh, pspec)
            params = jax.device_put(params, self._param_shardings)
        self.params = params

        mcfg = cfg

        # ---- legacy one-shot steps (static batch, lockstep lengths) ----
        @jax.jit
        def prefill(params, batch, cache):
            logits, _, cache = T.forward(mcfg, params, batch, cache=cache,
                                         cache_index=0, mode="prefill")
            return logits[:, -1], cache

        @jax.jit
        def decode(params, tokens, cache, index):
            logits, _, cache = T.forward(mcfg, params, {"tokens": tokens},
                                         cache=cache, cache_index=index,
                                         mode="decode")
            return logits[:, -1], cache

        # ---- pool-based steps (continuous batching) --------------------
        # the pool cache is donated: the caller rebinds pool.cache to the
        # result immediately, and without donation every token step would
        # materialize a second copy of the whole [L, n_slots, capacity, ...]
        # tree (the dominant memory/memcpy cost of the serving loop).
        # Under a mesh the bare jits below are replaced per pool geometry by
        # ``_steps_for`` with explicit in/out shardings.
        #
        # Every step ends in the pool's trailing operands
        # (``pool.step_operands``): none for a slab, whose slot axis IS the
        # layout; the page table for a ``PagedKVPool`` (DESIGN.md §15), whose
        # cache is a page arena [L, n_pages, page_size, ...].  The table goes
        # to ``T.forward(page_table=)``: each attention layer gathers its
        # slots' virtual slabs from the arena, runs the UNCHANGED slab math
        # and scatters the updated rows back — identical bytes in the
        # identical [slot, pos] layout at every attended position.  Every
        # page a dispatch writes is pinned through the pool (``ensure`` /
        # ``ensure_decode``) before it, so the table is invariant inside a
        # dispatch and a scan body closes over it.
        def prefill_chunk(params, tokens, cache, slot, offset, with_logits,
                          table=None):
            """tokens [1, C] into pool slot ``slot`` at position ``offset``;
            returns ([C, V] logits, updated pool cache).  ``with_logits=False``
            (non-final chunks, whose logits the caller discards) returns None
            logits — XLA dead-code-eliminates the whole lm-head matmul.

            A slab slot is sliced out, written and put back; a paged slot
            is the table row ``table`` [1, pages_per_slot] (its pages are
            scattered over the arena, so the whole arena rides through and
            only this slot's virtual slab is gathered and scattered)."""
            if table is not None:
                logits, _, cache = T.forward(
                    mcfg, params, {"tokens": tokens}, cache=cache,
                    cache_index=offset, mode="prefill_chunk",
                    page_table=table)
                return (logits[0] if with_logits else None), cache
            slot_cache = jax.tree_util.tree_map(
                lambda a: jax.lax.dynamic_slice_in_dim(a, slot, 1, axis=1),
                cache)
            logits, _, slot_cache = T.forward(
                mcfg, params, {"tokens": tokens}, cache=slot_cache,
                cache_index=offset, mode="prefill_chunk")
            with jax.named_scope("kv_write"):
                cache = jax.tree_util.tree_map(
                    lambda pool, upd: jax.lax.dynamic_update_slice_in_dim(
                        pool, upd, slot, axis=1),
                    cache, slot_cache)
            return (logits[0] if with_logits else None), cache

        def decode_slots_logits(params, tokens, cache, lengths, table=None):
            """tokens [n_slots, 1]; row i writes/attends at lengths[i].
            Returns the full [n_slots, V] logits — the diagnostic / scoring
            variant; the serving hot path uses the fused ``decode_slots``."""
            logits, _, cache = T.forward(mcfg, params, {"tokens": tokens},
                                         cache=cache, cache_index=lengths,
                                         mode="decode", page_table=table)
            return logits[:, -1], cache

        def decode_slots(params, tokens, cache, lengths, keys, temps,
                         table=None):
            """Fused decode + sample: one step for all slots, sampling on
            device (keys [n_slots, 2], temps [n_slots]).  Only the
            [n_slots] int32 sampled ids cross to the host — the logits are
            dead past ``sample_rows`` and never materialize off-device."""
            logits, _, cache = T.forward(mcfg, params, {"tokens": tokens},
                                         cache=cache, cache_index=lengths,
                                         mode="decode", page_table=table)
            return sample_rows(logits[:, -1], keys, temps), cache

        def decode_burst(params, cache, tokens, lengths, active, rem, keys,
                         temps, eos_ids, max_len, table=None):
            """K consecutive fused decode steps as one ``lax.scan``
            (DESIGN.md §11).  K is the leading dim of ``keys``
            [K, n_slots, 2] — the per-(request, step) key schedule the host
            precomputed from each request's ``step_key`` sequence, which is
            what makes a burst bit-identical to K single steps.

            Carry: (cache, tokens, lengths, active, rem).  Per step, active
            rows commit their input token's KV at ``lengths`` (then advance
            it), sample the next token, and re-evaluate their stop mask:
              * EOS       — sampled id == eos_ids[row] (>= 0),
              * length    — rem (tokens the row may still emit) hits 0,
              * capacity  — the committed length would exceed the slot
                            (mirrors the scheduler's defensive retire).
            Frozen rows ride along exactly like inactive slots: their
            lengths stop advancing, so their garbage writes land where the
            slot's next real write goes.  ys = (sampled [K, n_slots],
            was-active [K, n_slots]) — the host emits token (t, i) iff
            valid[t, i], in step-major order, reproducing the single-step
            emission sequence."""
            def step(carry, step_keys):
                cache, tokens, lengths, active, rem = carry
                logits, _, cache = T.forward(
                    mcfg, params, {"tokens": tokens[:, None]}, cache=cache,
                    cache_index=lengths, mode="decode", page_table=table)
                sampled = sample_rows(logits[:, -1], step_keys, temps)
                act = active.astype(jnp.int32)
                lengths = lengths + act
                rem = rem - act
                stop_eos = (eos_ids >= 0) & (sampled == eos_ids)
                still = active & ~stop_eos & (rem > 0) \
                    & (lengths < max_len - 1)
                tokens = jnp.where(active, sampled, tokens)
                return (cache, tokens, lengths, still, rem), (sampled, active)
            (cache, _, _, _, _), (toks, valid) = jax.lax.scan(
                step, (cache, tokens, lengths, active, rem), keys)
            return cache, toks, valid

        def verify_slots(params, tokens, cache, lengths, key_schedule,
                         temps, table=None):
            """Speculative verify (DESIGN.md §17): score ALL K+1 window
            positions of every row in ONE dispatch.  ``tokens``
            [n_slots, S] is each row's [last_committed, d_1..d_K] window;
            position j's logits predict token n_generated+j and are
            sampled with ``key_schedule[j]`` — the SAME per-(id,
            n_generated) keys a plain decode step would use — so the
            target's own samples g_0..g_K come back [S, n_slots] and the
            host accepts the longest prefix with g_{j-1} == d_j.

            The window runs as a ``lax.scan`` of EXACT plain decode
            steps *inside* the dispatch: step j is byte-for-byte the
            ``decode_burst`` step body (same s==1 forward, same KV
            write, same ``sample_rows``), which is the bit-identity
            argument ON EVERY GEOMETRY — under a mesh the s==1 steps hit
            the same Pallas kernels (fused decode attention, packed
            matvec) as the non-speculative scheduler, whereas a parallel
            S-wide scoring pass routes to bitwise-DIFFERENT kernels
            (einsum attention, the matmul block plan) whose last-bit
            logit differences temperature sampling amplifies into token
            flips.  One dispatch either way: the measured economics
            (dispatches/host-syncs per token) are the scan's; the
            single-weight-stream verify is the *priced deployment model*
            (``perfmodel.spec_round_latency``), not the host execution.
            Length commit/rollback stays host-side (the engine wrapper
            does NOT advance ``pool.lengths``)."""
            def step(carry, xs):
                cache, idx = carry
                tok, keys = xs
                logits, _, cache = T.forward(
                    mcfg, params, {"tokens": tok[:, None]}, cache=cache,
                    cache_index=idx, mode="decode", page_table=table)
                return ((cache, idx + 1),
                        sample_rows(logits[:, -1], keys, temps))
            (cache, _), sampled = jax.lax.scan(
                step, (cache, lengths), (tokens.T, key_schedule))
            return sampled, cache

        self._prefill = prefill
        self._decode = decode
        self._prefill_chunk_fn = prefill_chunk
        self._decode_slots_fn = decode_slots
        self._decode_slots_logits_fn = decode_slots_logits
        self._decode_burst_fn = decode_burst
        self._verify_slots_fn = verify_slots
        # single-device jits (mesh=None path; also the tracing baseline).
        # The burst and verify jits re-lower per distinct K (the scan
        # length is part of the traced shape); the scheduler's power-of-two
        # K policy bounds that to log2(max_burst) variants each.
        self._prefill_chunk = jax.jit(prefill_chunk, donate_argnums=(2,),
                                      static_argnums=(5,))
        self._decode_slots = jax.jit(decode_slots, donate_argnums=(2,))
        self._decode_slots_logits = jax.jit(decode_slots_logits,
                                            donate_argnums=(2,))
        self._decode_burst = jax.jit(decode_burst, donate_argnums=(1,))
        self._verify_slots = jax.jit(verify_slots, donate_argnums=(2,))

    # ------------------------------------------------------------------
    # Mesh-aware step construction (DESIGN.md §10)
    # ------------------------------------------------------------------
    def _declare_execution(self) -> None:
        """Declare this engine's execution policy (kernel mode + mesh +
        per-leaf kernel sharding specs) to ``kernels.ops``.  Called before
        every step invocation: jits trace on their first call, and the
        kernel dispatch is baked in at trace time.  ``kernel='auto'``
        leaves the process kernel mode untouched (backend default /
        whatever a driver pinned — under a mesh the default resolves to
        the shard_map'd pallas path); 'jnp' and 'pallas' pin it.  A
        single-device mesh declares as meshless (plain kernels; the
        shardings are trivial)."""
        from repro.kernels.ops import declare_execution
        declare_execution(
            kernel=None if self.policy.kernel == "auto" else self.policy.kernel,
            mesh=self.mesh if self._partitioned else None,
            weight_specs=self._kernel_weight_specs)

    @property
    def topology(self) -> Optional[Dict[str, int]]:
        """{'n_devices', 'dp', 'tp'} under a mesh, else None."""
        if self.mesh is None:
            return None
        tp = int(self.mesh.shape.get("model", 1))
        return {"n_devices": int(self.mesh.size),
                "dp": int(self.mesh.size) // tp, "tp": tp}

    def pool_shardings(self, pool: KVCachePool):
        """NamedSharding tree for ``pool``'s cache under this engine's
        mesh (None when meshless).  Derived from the pool's KV tier — the
        per-pool component of the precision policy — and its row count:
        a page arena's pages ride where a slab's slots do, so dp x tp
        sharding and donation survive the paging indirection unchanged."""
        if self.mesh is None:
            return None
        from repro.runtime import partitioning as PT
        spec = PT.serve_pool_pspec(self.cfg, self.mesh, pool.n_rows,
                                   kv_dtype=pool.kv_dtype)
        return PT.named(self.mesh, spec)

    def _steps_for(self, pool: KVCachePool):
        """(prefill_chunk, decode_slots, decode_slots_logits, decode_burst,
        verify_slots) jits for ``pool``'s geometry.

        Meshless: the bare jits.  Under a mesh: jits carrying explicit
        in/out shardings — cache in-sharding == out-sharding keeps donation
        alive; tokens / lengths / stop masks / sampled ids ride the slot
        (data) axis; the [K, n_slots, 2] burst and verify key schedules
        and their [K, n_slots] outputs carry the slot axis at position 1
        (``partitioning.serve_burst_pspec``); scalars and the [1, C] chunk
        tokens are replicated.  The pool's trailing step operands (the page
        table) ride the slot axis like the per-row keys; prefill's
        single-slot row is replicated like its chunk.  Cached per pool
        geometry and tier — the pool-varying components of the precision
        policy — so ONE engine holds compiled step sets for several KV
        tiers at once and per-request tier switching never recompiles a
        tier it has already served (DESIGN.md §12).  (Meshless, the bare
        jits do the same thing through jax.jit's own signature cache: a
        bf16 slab and a packed int8 slab are different pytree structures,
        hence different compiled specializations of one wrapper.)  Jits
        compile on first call, so a plain serving path never pays for
        the verify step.
        """
        self._declare_execution()
        if self.mesh is None:
            return (self._prefill_chunk, self._decode_slots,
                    self._decode_slots_logits, self._decode_burst,
                    self._verify_slots)
        steps = self._sharded_steps.get(pool.geometry)
        if steps is None:
            from repro.runtime import partitioning as PT
            mesh = self.mesh
            cache = self.pool_shardings(pool)
            spec = PT.serve_burst_pspec(mesh, pool.n_slots)
            rep = NamedSharding(mesh, P())
            tok = NamedSharding(mesh, P(spec["row"][0], None))
            row = NamedSharding(mesh, spec["row"])
            keys = NamedSharding(mesh, spec["row_keys"])
            sched = NamedSharding(mesh, spec["key_schedule"])
            out = NamedSharding(mesh, spec["burst_out"])
            n_ops = len(pool.step_operands())

            def jit(fn, donate, ins, outs, operand, **kw):
                return jax.jit(
                    fn, donate_argnums=(donate,),
                    in_shardings=(self._param_shardings, *ins)
                    + (operand,) * n_ops,
                    out_shardings=outs, **kw)
            steps = self._sharded_steps[pool.geometry] = (
                jit(self._prefill_chunk_fn, 2, (rep, cache, rep, rep),
                    (None, cache), rep, static_argnums=(5,)),
                jit(self._decode_slots_fn, 2, (tok, cache, row, keys, row),
                    (row, cache), keys),
                jit(self._decode_slots_logits_fn, 2, (tok, cache, row),
                    (None, cache), keys),
                jit(self._decode_burst_fn, 1,
                    (cache, row, row, row, row, sched, row, row, rep),
                    (cache, out, out), keys),
                jit(self._verify_slots_fn, 2,
                    (tok, cache, row, sched, row), (out, cache), keys))
        return steps

    # ------------------------------------------------------------------
    # Pool-based step primitives (the scheduler's interface)
    # ------------------------------------------------------------------
    def new_pool(self, n_slots: Optional[int] = None,
                 max_len: Optional[int] = None,
                 kv_dtype: Optional[str] = None) -> KVCachePool:
        """Build a slot pool at KV tier ``kv_dtype`` (default: the
        policy's tier).  With ``cache_budget_bytes`` set, the slot count
        is derived from KV bytes/token at the pool's tier — an int8/fp8
        pool fits ~2x the slots of bf16 in the same budget, which is what
        makes per-request tier switching a capacity lever (one engine can
        hold one pool per tier; see Scheduler ``tiers=``)."""
        tier = self.scfg.kv_dtype if kv_dtype is None \
            else validate_kv_tier(kv_dtype, self.cfg)
        max_len = max_len or self.scfg.max_len
        if self.scfg.paged:
            # page-granular budget accounting (DESIGN.md §15): the budget
            # buys an ARENA of pages, not worst-case max_len slots — slots
            # stay at the configured width (a slot is just a batch row; it
            # costs nothing until its request commits pages).
            page_size = self.scfg.page_size or self.scfg.prefill_chunk
            n_slots = n_slots or self.scfg.n_slots
            n_pages = None
            if self.scfg.cache_budget_bytes is not None:
                n_pages = pages_for_budget(
                    self.cfg, max_len, self.scfg.cache_budget_bytes,
                    kv_dtype=tier, page_size=page_size,
                    align=self.scfg.prefill_chunk)
            pool = PagedKVPool(self.cfg, n_slots, max_len, kv_dtype=tier,
                               align=self.scfg.prefill_chunk,
                               page_size=page_size, n_pages=n_pages)
        else:
            if n_slots is None:
                if self.scfg.cache_budget_bytes is not None:
                    n_slots = slots_for_budget(
                        self.cfg, max_len, self.scfg.cache_budget_bytes,
                        kv_dtype=tier, align=self.scfg.prefill_chunk)
                else:
                    n_slots = self.scfg.n_slots
            pool = KVCachePool(self.cfg, n_slots, max_len, kv_dtype=tier,
                               align=self.scfg.prefill_chunk)
        if self.mesh is not None:
            pool.place(self.pool_shardings(pool))
        return pool

    def _inject_fault(self, kind: str) -> Optional[str]:
        """Consult the fault-injection hook for one dispatch.  Returns
        'nan' when the dispatch's output should be poisoned (decode paths
        only — the caller corrupts the sampled ids so the scheduler's
        poisoned-output detector fires), raises ``StepFault`` for a
        killed dispatch, and returns None on the no-fault path."""
        fi = self.scfg.fault_injector
        if fi is None:
            return None
        self._fault_seq += 1
        mode = fi(kind, self._fault_seq)
        if not mode:
            return None
        if mode == "nan" and kind != "prefill":
            return "nan"
        raise StepFault(str(mode), f"{kind} dispatch #{self._fault_seq}")

    def pad_prompt(self, prompt: np.ndarray):
        """Prefill pre-pass: ONE int32 conversion + zero-pad to a whole
        number of prefill chunks.  Returns (padded [ceil(P/C)*C], P).  The
        per-chunk loop then slices views out of this buffer instead of
        allocating a fresh chunk per call (host allocation churn was the
        prefill path's per-chunk overhead)."""
        C = self.scfg.prefill_chunk
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        n = int(prompt.size)
        assert n > 0, "empty prompt"
        padded = np.zeros((-(-n // C) * C,), np.int32)
        padded[:n] = prompt
        return padded, n

    def prefill_chunk_into_slot(self, pool: KVCachePool, slot: int,
                                prompt: np.ndarray, offset: int, *,
                                prompt_len: Optional[int] = None,
                                need_logits: bool = True):
        """Write prompt[offset : offset+C] into ``slot``.  For the prompt's
        final chunk, returns the [C, V] chunk logits (pad positions carry
        garbage — callers index the true last position); earlier chunks
        return None and skip the lm-head compute entirely.  Advances
        ``pool.lengths[slot]``.

        With ``prompt_len`` given, ``prompt`` must already be the
        chunk-padded buffer from ``pad_prompt`` (the scheduler pads once at
        admission); without it, the legacy raw-prompt interface pads here.
        ``need_logits=False`` skips the lm-head even on the final chunk
        (the preempt-resume replay path: those tokens' next-token samples
        were already delivered, only their KV must be recomputed) — it
        reuses the non-final chunk's compiled variant, so no extra jit.
        """
        C = self.scfg.prefill_chunk
        if prompt_len is None:
            prompt, prompt_len = self.pad_prompt(prompt)
        n = min(C, prompt_len - offset)
        assert n > 0, (offset, prompt_len)
        assert offset + n <= pool.max_len, "prompt exceeds slot capacity"
        chunk = prompt[offset:offset + C][None]       # view, no allocation
        final = (offset + n >= prompt_len) and need_logits
        self._inject_fault("prefill")
        prefill_chunk = self._steps_for(pool)[0]
        # pin the chunk's write window before the jitted write (paged
        # pools: fresh pages, or COW of a shared page on a full-cover
        # prefix hit)
        pool.ensure(slot, offset + C)
        logits, pool.cache = prefill_chunk(
            self.params, jnp.asarray(chunk), pool.cache, jnp.int32(slot),
            jnp.int32(offset), final,
            *map(jnp.asarray, pool.step_operands(slot)))
        pool.lengths[slot] = offset + n
        if not final:
            return None
        with span("serve.sync", what="logits"):
            return jax.block_until_ready(logits)

    def prefill_into_slots(self, pool: KVCachePool, slots: Sequence[int],
                           prompts: Sequence[np.ndarray]) -> List:
        """Full chunked prefill of each (slot, prompt); returns the [V]
        logits at each prompt's true last position."""
        C = self.scfg.prefill_chunk
        out = []
        for slot, prompt in zip(slots, prompts):
            padded, n = self.pad_prompt(prompt)
            logits = None
            for off in range(0, n, C):
                logits = self.prefill_chunk_into_slot(pool, slot, padded,
                                                      off, prompt_len=n)
            out.append(logits[(n - 1) % C])
        return out

    def decode_slots(self, pool: KVCachePool, tokens: np.ndarray,
                     keys: Optional[np.ndarray] = None,
                     temperatures: Optional[np.ndarray] = None) -> np.ndarray:
        """One fused decode+sample step over every pool slot.  ``tokens``
        [n_slots]; row i is written at pool.lengths[i].  Sampling happens
        ON DEVICE (``keys`` [n_slots, 2] uint32 / ``temperatures``
        [n_slots]; both default to zeros = greedy) and only the [n_slots]
        int32 sampled ids come back — the logits never leave the device.
        The caller pins the rows' write positions first
        (``pool.ensure_decode``) and commits the write by incrementing
        ``pool.lengths`` for the rows it considers active."""
        n = pool.n_slots
        tokens = np.asarray(tokens, np.int32).reshape(n, 1)
        if keys is None:
            keys = np.zeros((n, 2), np.uint32)
        if temperatures is None:
            temperatures = np.zeros((n,), np.float32)
        poison = self._inject_fault("decode")
        decode_slots = self._steps_for(pool)[1]
        toks, pool.cache = decode_slots(
            self.params, jnp.asarray(tokens), pool.cache,
            jnp.asarray(pool.lengths), jnp.asarray(keys, jnp.uint32),
            jnp.asarray(temperatures, jnp.float32),
            *map(jnp.asarray, pool.step_operands()))
        with span("serve.sync", what="tokens"):
            toks = np.asarray(toks)
        if poison is not None:
            # poisoned-output simulation: out-of-vocab ids, as a NaN-
            # saturated sampler would produce — the scheduler's validity
            # guard (not this return path) is what must catch them
            toks = np.full_like(toks, -1)
        return toks

    def decode_slots_with_logits(self, pool: KVCachePool,
                                 tokens: np.ndarray) -> np.ndarray:
        """The logits-returning decode variant (score / diagnostic paths):
        same write semantics as ``decode_slots`` but returns the full
        [n_slots, V] logits — one host transfer of the whole logit block."""
        tokens = np.asarray(tokens, np.int32).reshape(pool.n_slots, 1)
        decode_logits = self._steps_for(pool)[2]
        logits, pool.cache = decode_logits(
            self.params, jnp.asarray(tokens), pool.cache,
            jnp.asarray(pool.lengths),
            *map(jnp.asarray, pool.step_operands()))
        with span("serve.sync", what="logits"):
            return jax.block_until_ready(logits)

    def decode_burst(self, pool: KVCachePool, tokens: np.ndarray,
                     key_schedule: np.ndarray, temperatures: np.ndarray,
                     active: np.ndarray, remaining: np.ndarray,
                     eos_ids: np.ndarray):
        """K consecutive decode steps on device — ONE dispatch, ONE host
        sync (DESIGN.md §11).  K = key_schedule.shape[0]; row i of
        ``key_schedule[t]`` must be request i's ``step_key`` for its
        (n_generated + t)-th token so the burst is bit-identical to K
        single steps.  ``active`` [n_slots] bool marks live decode rows;
        ``remaining`` [n_slots] int32 is each row's max-new-tokens budget
        left; ``eos_ids`` [n_slots] int32 (-1 = never).  Rows that hit a
        stop condition freeze mid-burst (their lengths stop advancing).
        The caller pins each row's K-step write window first
        (``pool.ensure_decode(slots, K, rems)``).

        Returns (tokens [K, n_slots] int32, valid [K, n_slots] bool) as
        host arrays; token (t, i) was emitted iff valid[t, i].  Commits
        ``pool.lengths`` for every emitted token (unlike single-step
        ``decode_slots``, where the caller commits)."""
        K, n = key_schedule.shape[0], pool.n_slots
        assert key_schedule.shape == (K, n, 2), key_schedule.shape
        tokens = np.asarray(tokens, np.int32).reshape(n)
        poison = self._inject_fault("burst")
        decode_burst = self._steps_for(pool)[3]
        pool.cache, toks, valid = decode_burst(
            self.params, pool.cache, jnp.asarray(tokens),
            jnp.asarray(pool.lengths), jnp.asarray(active, bool),
            jnp.asarray(remaining, jnp.int32),
            jnp.asarray(key_schedule, jnp.uint32),
            jnp.asarray(temperatures, jnp.float32),
            jnp.asarray(eos_ids, jnp.int32), jnp.int32(pool.max_len),
            *map(jnp.asarray, pool.step_operands()))
        with span("serve.sync", what="tokens"):       # the burst's one sync
            toks = np.asarray(toks)
            valid = np.asarray(valid)
        if poison is not None:
            toks = np.full_like(toks, -1)
        pool.lengths += valid.sum(axis=0).astype(np.int32)
        return toks, valid

    def verify_slots(self, pool: KVCachePool, tokens: np.ndarray,
                     key_schedule: np.ndarray,
                     temperatures: np.ndarray) -> np.ndarray:
        """Speculative verify over every pool slot (DESIGN.md §17).
        ``tokens`` [n_slots, S]: row i's window [last_committed, d_1..d_K]
        written at pool.lengths[i]..+S-1 (the caller pins that window,
        ``pool.ensure_decode(slots, S, rems)``); ``key_schedule``
        [S, n_slots, 2] carries each row's real step keys for tokens
        n_generated..+K.  Returns the target's sampled ids [S, n_slots] int32 — g_j at
        position j.  Does NOT commit ``pool.lengths``: the caller accepts
        the longest agreeing prefix and sets lengths to the emitted count
        (which IS the rollback — positions past the committed length are
        garbage-but-masked, exactly like inactive-slot decode writes)."""
        n = pool.n_slots
        tokens = np.asarray(tokens, np.int32).reshape(n, -1)
        s = tokens.shape[1]
        assert key_schedule.shape == (s, n, 2), key_schedule.shape
        poison = self._inject_fault("verify")
        verify_slots = self._steps_for(pool)[4]
        sampled, pool.cache = verify_slots(
            self.params, jnp.asarray(tokens), pool.cache,
            jnp.asarray(pool.lengths),
            jnp.asarray(key_schedule, jnp.uint32),
            jnp.asarray(temperatures, jnp.float32),
            *map(jnp.asarray, pool.step_operands()))
        with span("serve.sync", what="tokens"):   # the round's verify sync
            sampled = np.asarray(sampled)
        if poison is not None:
            sampled = np.full_like(sampled, -1)
        return sampled

    # ------------------------------------------------------------------
    # One-shot generation (backwards-compatible wrapper)
    # ------------------------------------------------------------------
    def generate(self, batch: Dict, *, max_new_tokens: int,
                 seed: int = 0, obs=None) -> Dict:
        """batch: {'tokens': [B, S]} (+ stubs).  Returns generated ids
        [B, T] (post-EOS positions masked to 0), per-row lengths and finish
        reasons.  ``obs``: optional ``repro.obs.Observability`` bundle
        threaded into the internal Scheduler (DESIGN.md §13); the legacy
        static-batch families have no scheduler and ignore it."""
        if self.cfg.family in SCHEDULABLE_FAMILIES:
            return self._generate_scheduled(batch, max_new_tokens, seed,
                                            obs=obs)
        return self._generate_legacy(batch, max_new_tokens, seed)

    def _generate_scheduled(self, batch, max_new_tokens: int, seed: int,
                            obs=None):
        from .request import Request, SamplingParams
        from .scheduler import Scheduler

        tokens = np.asarray(batch["tokens"], np.int32)
        b, s = tokens.shape
        assert s + max_new_tokens <= self.scfg.max_len, \
            "grow ServeConfig.max_len"
        sched = Scheduler(self, obs=obs)
        reqs = [sched.submit(Request(
            prompt=tokens[i],
            sampling=SamplingParams(temperature=self.scfg.temperature,
                                    max_new_tokens=max_new_tokens,
                                    eos_id=self.scfg.eos_id, seed=seed)))
            for i in range(b)]
        sched.run()
        width = max(r.n_generated for r in reqs)
        gen = np.zeros((b, width), np.int32)
        lengths = np.zeros((b,), np.int32)
        for i, r in enumerate(reqs):
            gen[i, :r.n_generated] = r.output_tokens
            lengths[i] = r.n_generated
        m = sched.metrics
        return {"generated": gen, "prompt_len": s, "batch": b,
                "lengths": lengths,
                "finish_reasons": [r.finish_reason for r in reqs],
                # burst accounting (DESIGN.md §11): how amortized the
                # decode path actually ran for this generation
                "decode_dispatches": m.decode_dispatches,
                "decode_token_steps": m.decode_token_steps,
                "host_syncs": sched.n_host_syncs,
                "burst_hist": dict(m.burst_hist)}

    # ---- legacy static-batch loop (ssm / hybrid / audio / vlm) ---------
    def _sample(self, logits, key):
        if self.scfg.temperature <= 0:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return jax.random.categorical(
            key, logits / self.scfg.temperature).astype(jnp.int32)

    def _generate_legacy(self, batch, max_new_tokens: int, seed: int):
        self._declare_execution()
        cfg, scfg = self.cfg, self.scfg
        tokens = jnp.asarray(batch["tokens"], jnp.int32)
        b, s = tokens.shape
        prefix = cfg.n_patches if cfg.family == "vlm" else 0
        assert s + max_new_tokens <= scfg.max_len, "grow ServeConfig.max_len"

        cache = T.init_cache(cfg, b, prefix + s + max_new_tokens,
                             kv_dtype=scfg.kv_dtype)
        last_logits, cache = self._prefill(self.params, batch, cache)

        key = jax.random.PRNGKey(seed)
        out: List[np.ndarray] = []
        index = prefix + s
        tok = self._sample(last_logits, key)
        out.append(np.asarray(tok))
        finished = np.zeros((b,), bool)
        for i in range(max_new_tokens - 1):
            key, sub = jax.random.split(key)
            logits, cache = self._decode(self.params, tok[:, None], cache,
                                         jnp.int32(index + i))
            tok = self._sample(logits, sub)
            out.append(np.asarray(tok))
            if scfg.eos_id >= 0:
                finished |= np.asarray(tok) == scfg.eos_id
                if finished.all():   # whole batch retired: stop burning steps
                    break
        gen = np.stack(out, axis=1)
        lengths = np.full((b,), gen.shape[1], np.int32)
        reasons = ["length"] * b
        if scfg.eos_id >= 0:
            # mask everything after each row's first EOS (a static batch
            # cannot retire rows early, but their post-EOS garbage must not
            # leak into the output)
            eos = gen == scfg.eos_id
            seen_before = np.cumsum(eos, axis=1) - eos
            keep = seen_before == 0
            gen = np.where(keep, gen, 0)
            lengths = keep.sum(1).astype(np.int32)
            reasons = ["eos" if eos[i].any() else "length" for i in range(b)]
        return {"generated": gen, "prompt_len": s, "batch": b,
                "lengths": lengths, "finish_reasons": reasons}

    def score(self, batch: Dict) -> np.ndarray:
        """Teacher-forced mean NLL per row (serving-quality check)."""
        self._declare_execution()
        logits, _, _ = T.forward(self.cfg, self.params, batch, mode="train")
        if self.cfg.family == "vlm":
            logits = logits[:, self.cfg.n_patches:]
        lf = jnp.asarray(logits, jnp.float32)
        labels = batch["labels"]
        lse = jax.scipy.special.logsumexp(lf, axis=-1)
        gold = jnp.take_along_axis(lf, labels[..., None].clip(0), -1)[..., 0]
        mask = (labels >= 0)
        nll = jnp.where(mask, lse - gold, 0.0).sum(-1) / mask.sum(-1)
        return np.asarray(nll)
