"""Logical-axis partitioning rules (DP / TP / EP / SP) for every arch.

Design (DESIGN.md §5, 1000+-node posture):
  * batch        -> ('pod', 'data')   pure DP across pods; only the gradient
                                       all-reduce crosses pod ICI
  * heads / d_ff / experts / vocab -> 'model'   (TP / EP)
  * KV-cache sequence -> 'model' (+ 'data' when batch can't shard) — the
                         flash-decode split-KV axis (SP)
  * FSDP (train only): each weight's non-TP dim sharded over 'data'
    (ZeRO-3; GSPMD inserts the per-layer all-gathers under the layer scan,
    overlapping with compute)

The rules are *name-driven*: every Maker leaf was created with a logical
name ("attn.wq", "moe.w_gate", ...) and the table below maps
(name, logical dim) -> mesh axis.  ``param_specs`` runs the same Maker walk
as parameter construction, so specs and parameters cannot drift.

Divisibility guards: a dim is only sharded if its size divides the mesh
axis (e.g. GQA with 4 KV heads on a 16-way model axis leaves K/V projection
outputs replicated — the paper-shape-correct choice).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.models.common import PspecMaker
from repro.models.transformer import ModelConfig, build_params, init_cache


@dataclasses.dataclass(frozen=True)
class AxisRules:
    """Mesh-axis names + sizes for rule resolution."""
    batch_axes: Tuple[str, ...]       # ('data',) or ('pod','data')
    model_axis: str = "model"
    model_size: int = 16
    fsdp_axis: Optional[str] = None   # 'data' for training, None for serving

    @property
    def data_axis(self) -> str:
        return self.batch_axes[-1]


def rules_from_mesh(mesh: Mesh, *, train: bool) -> AxisRules:
    axes = list(mesh.axis_names)
    model = "model"
    batch_axes = tuple(a for a in axes if a != model)
    return AxisRules(batch_axes=batch_axes, model_axis=model,
                     model_size=mesh.shape[model],
                     fsdp_axis="data" if train else None)


# (name-prefix) -> (axis role for dim0, dim1); roles resolved per-config.
#   'tp'   -> model axis (if divisible)
#   'fsdp' -> fsdp axis (train only, if divisible)
#   None   -> replicated
_W_RULES: Dict[str, Tuple[Optional[str], Optional[str]]] = {
    "embed": ("tp", "fsdp"),          # [vocab, d]
    "lm_head": ("fsdp", "tp"),        # [d, vocab]
    "enc_pos": (None, "fsdp"),
    "dec_pos": (None, "fsdp"),
    "attn.wq": ("fsdp", "tp"),
    "attn.wk": ("fsdp", "tp"),
    "attn.wv": ("fsdp", "tp"),
    "attn.wo": ("tp", "fsdp"),
    # MLA
    "attn.w_dq": ("fsdp", "tp"),
    "attn.w_uq": ("tp", "tp2"),       # K = q_lora (tp'd by w_dq), N = heads
    "attn.w_dkv": ("fsdp", None),     # latent stays replicated (it is cached)
    "attn.w_uk": ("fsdp", "tp"),
    "attn.w_uv": ("fsdp", "tp"),
    # FFN
    "ffn.w_gate": ("fsdp", "tp"),
    "ffn.w_up": ("fsdp", "tp"),
    "ffn.w_in": ("fsdp", "tp"),
    "ffn.w_down": ("tp", "fsdp"),
    "ffn.w_out": ("tp", "fsdp"),
    # MoE (stack carries the expert dim -> 'model'; see _stack_rule)
    "moe.router": ("fsdp", None),
    "moe.w_gate": ("fsdp", None),
    "moe.w_up": ("fsdp", None),
    "moe.w_down": (None, "fsdp"),
    # SSM
    "ssm.w_zx": ("fsdp", "tp"),       # z/x head-aligned
    "ssm.w_up": ("fsdp", "tp"),
    "ssm.w_bc": ("fsdp", None),       # B/C shared across heads
    "ssm.w_dt": ("fsdp", None),
    "ssm.w_q": ("fsdp", "tp"),
    "ssm.w_k": ("fsdp", "tp"),
    "ssm.w_v": ("fsdp", "tp"),
    "ssm.w_if": ("fsdp", None),
    "ssm.w_gates": ("fsdp", "tp"),
    "ssm.w_out": ("tp", "fsdp"),
}

# vectors / norms / conv tables: channel dim rule (dim 0 of the spec call)
_V_RULES: Dict[str, Optional[str]] = {
    "ssm.conv_x": None,    # [W, di] — dim1 handled via table rule below
}

# Attention projections whose flat [.., H*Dh] dim is reshaped to heads in
# the forward pass: that dim shards at HEAD granularity only
# (name -> (head-carrying dim, 'q' = n_heads | 'kv' = n_kv_heads)).  The
# raw dim size h*dh often divides a mesh axis that the head count does not
# (2 KV heads x 16 dims on a 4-way axis) — sharding there splits inside a
# head, which the docstring above already forbids in intent and which the
# reshape-under-2D-mesh path miscompiles in practice (DESIGN.md §10).
# Non-head dims of these leaves (e.g. w_uq's q_lora K dim) are untouched.
_HEAD_ALIGNED: Dict[str, Tuple[int, str]] = {
    "attn.wq": (1, "q"), "attn.wk": (1, "kv"), "attn.wv": (1, "kv"),
    "attn.wo": (0, "q"),
    "attn.w_uq": (1, "q"), "attn.w_uk": (1, "q"), "attn.w_uv": (1, "q"),
}


def _divides(n: int, axis_size: int) -> bool:
    return n % axis_size == 0 and n >= axis_size


class _ShapeProbe:
    """Records each leaf's logical dims so divisibility can be checked."""

    def __init__(self):
        self.dims: Dict[str, Tuple[int, ...]] = {}


def make_param_rule(cfg: ModelConfig, rules: AxisRules, dim_sizes):
    """Returns rule(name, dim) -> axis-or-None for PspecMaker."""
    model, fsdp = rules.model_axis, rules.fsdp_axis
    msize = rules.model_size
    fsize = dim_sizes.get("__fsdp_size__", 0)

    def resolve(role: Optional[str], size: int):
        if role in ("tp", "tp2") and _divides(size, msize):
            return model
        if role == "fsdp" and fsdp is not None and _divides(size, fsize):
            return fsdp
        return None

    def rule(name: str, dim: int):
        base = name.split("@")[0]
        if base.endswith(".b") and base[:-2] in _W_RULES:
            # a linear's bias lies along its weight's output (N) axis
            return rule(base[:-2], 1)
        roles = _W_RULES.get(base)
        if roles is None:
            # norms / vectors / tables: replicate (small), except conv
            # channel dims which follow their block's TP layout
            if name in ("ssm.conv_x",) and dim == 1:
                return resolve("tp", dim_sizes.get((name, 1), 0))
            return None
        size = dim_sizes.get((name, dim), 0)
        ax = resolve(roles[dim], size)
        # head-granularity guard: the head-carrying dim of an attention
        # projection shards over 'model' only when the head COUNT divides
        if ax == model and _HEAD_ALIGNED.get(base, (None,))[0] == dim:
            heads = cfg.n_kv_heads if _HEAD_ALIGNED[base][1] == "kv" \
                else cfg.n_heads
            if not _divides(heads, msize):
                ax = None
        # Quantized leaves, K axis (dim 0): a shard boundary must land on
        # BOTH an int32 code-word boundary and a scale-group boundary, and
        # packed codes and group scales must shard in lockstep (a shard has
        # to own the scale rows of its own K rows).  Sharding the packed
        # array can never split a word (each word is one element), so the
        # binding constraint is the twin leaf: shard K only when the twin's
        # K dim divides the axis the same way — otherwise replicate
        # (DESIGN.md §10).
        if ax is not None and dim == 0 and "@" in name:
            twin = base + ("@scales" if name.endswith("@packed")
                           else "@packed")
            if resolve(roles[0], dim_sizes.get((twin, 0), 0)) != ax:
                ax = None
        # never double-assign the same axis to both dims
        if dim == 1 and ax is not None:
            ax0 = rule(name, 0)
            if ax0 == ax:
                return None
        return ax

    return rule


def _collect_dim_sizes(cfg: ModelConfig, plan: Optional[Dict] = None) -> Dict:
    """Walk with a recording maker to learn each leaf's actual dims
    (including the packed-code / scale array dims of quantized leaves).
    ``plan`` applies the same per-name scheme overrides QuantMaker honors,
    so recorded dims track the checkpoint that was actually built."""
    from repro.quant.schemes import effective_group, get_scheme
    sizes: Dict = {}

    class Probe(PspecMaker):
        def __init__(self):
            super().__init__(rule=lambda n, d: None, quantize=False)

        def dense(self, name, stack, k, n, scheme=None):
            if plan:
                scheme = plan.get(name, scheme)
            sizes[(name, 0)] = k
            sizes[(name, 1)] = n
            if scheme is not None and scheme != "bf16":
                s = get_scheme(scheme)
                kp = k // (32 // s.weight_bits) if s.packed else k
                sizes[(name + "@packed", 0)] = kp
                sizes[(name + "@packed", 1)] = n
                sizes[(name + "@scales", 0)] = k // effective_group(
                    s.group_size, k)
                sizes[(name + "@scales", 1)] = n
            return super().dense(name, stack, k, n, scheme)

        def table(self, name, stack, rows, cols, scale=0.02):
            sizes[(name, 0)] = rows
            sizes[(name, 1)] = cols
            return super().table(name, stack, rows, cols, scale)

    build_params(cfg, Probe())
    return sizes


def _stack_axes(cfg: ModelConfig, rules: AxisRules, name: str,
                n_stack: int) -> Tuple[Optional[str], ...]:
    """Axes for the leading stack dims (layer stack + expert dim)."""
    if name.startswith("moe.w_") and n_stack >= 1:
        # last stack dim is the expert dim -> EP over 'model'
        ep = rules.model_axis if _divides(cfg.n_experts, rules.model_size) else None
        return (None,) * (n_stack - 1) + (ep,)
    return (None,) * n_stack


def param_specs(cfg: ModelConfig, mesh: Mesh, *, train: bool,
                quantize: Optional[bool] = None,
                plan: Optional[Dict[str, str]] = None,
                policy=None):
    """PartitionSpec tree matching build_params' structure exactly.

    ``plan``: the same per-name scheme overrides given to ``QuantMaker`` —
    specs must be built with the plan the checkpoint was built with, or the
    two trees diverge wherever the plan flips a leaf between dense and
    packed.  ``policy``: a ``quant.policy.PrecisionPolicy`` — the unified
    spelling of the same contract (DESIGN.md §12); its resolved plan is
    used, so shardings derive from the single datatype-adaptive object the
    checkpoint and the serving engine share.  Give one or the other."""
    if policy is not None:
        if plan is not None:
            raise ValueError("give either plan= or policy=, not both")
        plan = policy.resolved_plan(cfg)
    return build_params(cfg, spec_maker(cfg, mesh, train=train,
                                        quantize=quantize, plan=plan))


def spec_maker(cfg: ModelConfig, mesh: Mesh, *, train: bool,
               quantize: Optional[bool] = None,
               plan: Optional[Dict[str, str]] = None) -> PspecMaker:
    """The Maker behind ``param_specs``, answering one leaf at a time (its
    ``mesh`` attribute names the mesh).  ``QuantMaker(..., specs=
    spec_maker(cfg, mesh, train=False, plan=plan))`` creates every leaf
    directly in the shards the serving engine expects (give both the same
    plan)."""
    rules = rules_from_mesh(mesh, train=train)
    sizes = _collect_dim_sizes(cfg, plan)
    if rules.fsdp_axis is not None:
        sizes["__fsdp_size__"] = mesh.shape[rules.fsdp_axis]
    rule = make_param_rule(cfg, rules, sizes)
    q = (not train) if quantize is None else quantize

    class Maker(PspecMaker):
        def __init__(self):
            super().__init__(rule=rule, quantize=q)
            self.mesh = mesh

        def dense(self, name, stack, k, n, scheme=None):
            if plan:
                scheme = plan.get(name, scheme)
            return super().dense(name, stack, k, n, scheme)

        def _spec(self, name, stack, dims):
            stack_ax = _stack_axes(cfg, rules, name, len(stack))
            parts = list(stack_ax) + [self.rule(name, d) for d in range(dims)]
            # EP consumed 'model': drop TP on the weight dims of expert mats
            if any(a == rules.model_axis for a in stack_ax):
                parts = list(stack_ax) + [
                    p if p != rules.model_axis else None
                    for p in parts[len(stack_ax):]]
            return P(*parts)

    return Maker()


def serve_weight_kernel_specs(cfg: ModelConfig, mesh: Mesh, *,
                              plan: Optional[Dict[str, str]] = None,
                              policy=None) -> Dict[str, Dict]:
    """Per-leaf mesh axes for the shard_map'd weight kernels (DESIGN.md
    §14): ``{leaf name: {'packed': (k_ax, n_ax), 'scales': (k_ax, n_ax)}}``
    for every quantized leaf the kernel path can serve.

    The axes are exactly the leaf's ``param_specs`` storage axes with the
    leading stack dims stripped — the kernel runs on the per-layer slice
    inside the scan, and its shard_map in_specs must match where the codes
    and scales already live (no resharding on the hot path).  The same
    make_param_rule produces both, so kernel specs and storage specs
    cannot drift — in particular K only shards where code words and scale
    groups split in lockstep (the joint-boundary rule).

    Stacked-expert (``moe.*``) leaves are excluded: the expert vmap wraps
    the kernel call and shard_map cannot nest inside it — those sites fall
    back to the jnp path per-site (kernels/ops.py warns once per site).
    """
    if policy is not None:
        if plan is not None:
            raise ValueError("give either plan= or policy=, not both")
        plan = policy.resolved_plan(cfg)
    rules = rules_from_mesh(mesh, train=False)
    sizes = _collect_dim_sizes(cfg, plan)
    rule = make_param_rule(cfg, rules, sizes)
    specs: Dict[str, Dict] = {}

    class Probe(PspecMaker):
        def __init__(self):
            super().__init__(rule=rule, quantize=True)

        def dense(self, name, stack, k, n, scheme=None):
            if plan:
                scheme = plan.get(name, scheme)
            if scheme is not None and scheme != "bf16" \
                    and not name.startswith("moe."):
                specs[name] = {
                    "packed": (rule(name + "@packed", 0),
                               rule(name + "@packed", 1)),
                    "scales": (rule(name + "@scales", 0),
                               rule(name + "@scales", 1)),
                }
            return super().dense(name, stack, k, n, scheme)

    build_params(cfg, Probe())
    return specs


# ---------------------------------------------------------------------------
# Input / cache / state specs
# ---------------------------------------------------------------------------
def batch_pspec(cfg: ModelConfig, rules: AxisRules, batch_size: int,
                mesh: Mesh):
    """PartitionSpecs for a train/serve input batch dict."""
    bax = rules.batch_axes
    bsize = int(np.prod([mesh.shape[a] for a in bax]))
    if batch_size % bsize != 0:   # small serve batches: fewest axes that fit
        bax = tuple(a for a in bax if batch_size % mesh.shape[a] == 0)[-1:]
    b = P(bax if bax else None, None)
    specs = {"tokens": b, "labels": b}
    if cfg.family == "vlm":
        specs["patches"] = P(bax, None, None)
    if cfg.family == "audio":
        specs["frames"] = P(bax, None, None)
    return specs


def cache_pspec(cfg: ModelConfig, rules: AxisRules, batch_size: int,
                mesh: Mesh):
    """PartitionSpecs for the decode cache: SP over the KV sequence axis.

    KV caches [L?, B, S, H, D]: B over batch axes when divisible, S over
    'model' (flash-decode split-KV).  When B == 1 (long_500k) the sequence
    axis takes BOTH axes.  Recurrent states shard B over data and heads
    over 'model' when divisible.
    """
    bax = rules.batch_axes
    bsize = int(np.prod([mesh.shape[a] for a in bax]))
    b_ok = batch_size % bsize == 0 and batch_size >= bsize
    b_ax = bax if b_ok else None
    s_ax = ("model",) if b_ok else (bax + ("model",))

    def kv_spec(nstack, ndim_tail):
        # [stack..., B, S, (H, D) or (latent,)]
        return P(*([None] * nstack), b_ax, s_ax, *([None] * ndim_tail))

    def state_spec(nstack, shape):
        # SSMState arrays [stack..., B, nh, ...]: shard nh over model
        nh = shape[nstack + 1] if len(shape) > nstack + 1 else 0
        nh_ax = "model" if _divides(nh, rules.model_size) else None
        tail = [None] * (len(shape) - nstack - 2)
        return P(*([None] * nstack), b_ax, nh_ax, *tail)

    abstract = init_cache(cfg, batch_size, 8, abstract=True)

    def classify(path, leaf):
        shape = leaf.shape
        names = [getattr(p, 'key', getattr(p, 'name', str(p))) for p in path]
        path_s = "/".join(str(n) for n in names)
        # count leading stack dims: dims before the batch-sized dim
        nstack = 0
        for d in shape:
            if d == batch_size:
                break
            nstack += 1
        if nstack >= len(shape):   # no batch dim found — replicate
            return P()
        if "conv" in path_s:
            return P(*([None] * nstack), b_ax, None, None)
        if "state" in path_s or "slstm" in path_s or path_s.endswith("m") \
                or "Hs" in path_s or "ns" in path_s:
            return state_spec(nstack, shape)
        if path_s == "enc":
            return P(b_ax, None, None)
        # KV-style: [stack..., B, S, ...]
        return kv_spec(nstack, len(shape) - nstack - 2)

    return jax.tree_util.tree_map_with_path(classify, abstract)


def serve_pool_pspec(cfg: ModelConfig, mesh: Mesh, n_slots: int, *,
                     kv_dtype="bf16"):
    """PartitionSpecs for the serving KV pool tree
    ``[L, n_slots, capacity, ...]`` (DESIGN.md §10).  ``kv_dtype`` is the
    pool's KV tier — the per-pool component of the ``PrecisionPolicy``
    (DESIGN.md §12): the engine passes ``pool.kv_dtype``, which may be a
    per-request tier rather than the policy's default.

    Contract (differs from ``cache_pspec``, which serves the static
    one-shot shapes):
      * slots (the continuous-batching batch dim) -> data axis — each DP
        shard owns a contiguous band of pool rows for a request's lifetime;
      * heads -> 'model' — TP attention keeps each shard's heads local
        end-to-end (replicated when ``n_kv_heads`` does not divide);
      * the sequence axis stays LOCAL: prefill-chunk and per-row decode
        writes land at *traced* offsets, and sharding S would turn every
        cache write into cross-shard traffic;
      * the packed code-word dim of a quantized slab never shards (4 codes
        per int32 word along d_head); its scales twin drops that dim.

    Divisibility guards mirror ``param_specs``: an axis that does not
    divide stays replicated rather than padded.
    """
    from repro.models import attention as A
    if cfg.family not in ("dense", "moe", "vlm"):
        raise ValueError(
            f"serve_pool_pspec covers slot-pool families, not {cfg.family!r}")
    rules = rules_from_mesh(mesh, train=False)
    dax = rules.data_axis
    slot_ax = dax if _divides(n_slots, mesh.shape[dax]) else None
    if cfg.use_mla:
        per_layer = A.mla_cache_pspec(cfg.mla_cfg(), slot_ax)
    else:
        head_ax = rules.model_axis \
            if _divides(cfg.n_kv_heads, rules.model_size) else None
        per_layer = A.gqa_cache_pspec(cfg.attn_cfg(), kv_dtype,
                                      slot_ax, head_ax)
    # prepend the (L,) layer-stack dim (never sharded: lax.scan carries it)
    return jax.tree_util.tree_map(lambda p: P(None, *p), per_layer,
                                  is_leaf=lambda x: isinstance(x, P))


def serve_burst_pspec(mesh, n_slots: int) -> Dict[str, P]:
    """PartitionSpecs for the decode fast-path carries that ride the slot
    axis (DESIGN.md §11) — the non-cache inputs/outputs of the fused
    ``decode_slots`` and ``decode_burst`` jits:

      * ``row``          [n_slots]       — tokens / lengths / active mask /
                                           remaining-budget / temperatures /
                                           eos ids / sampled ids
      * ``row_keys``     [n_slots, 2]    — per-row PRNG keys (single step)
      * ``key_schedule`` [K, n_slots, 2] — the burst's precomputed
                                           per-(request, step) key schedule;
                                           the step axis stays local (it is
                                           the ``lax.scan`` axis)
      * ``burst_out``    [K, n_slots]    — stacked sampled ids / valid masks

    The slot axis follows the SAME divisibility guard as
    ``serve_pool_pspec``: it shards over the data axis iff ``n_slots``
    divides it, so burst carries and the pool cache always agree on where
    a slot row lives (a mismatch would resharding-copy the cache every
    step and kill donation)."""
    rules = rules_from_mesh(mesh, train=False)
    dax = rules.data_axis
    slot_ax = dax if _divides(n_slots, mesh.shape[dax]) else None
    return {
        "row": P(slot_ax),
        "row_keys": P(slot_ax, None),
        "key_schedule": P(None, slot_ax, None),
        "burst_out": P(None, slot_ax),
    }


def named(mesh: Mesh, spec_tree):
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), spec_tree,
        is_leaf=lambda x: isinstance(x, P))
