"""PrecisionPolicy — the one datatype-adaptive contract (DESIGN.md §12).

XtraMAC's core claim is a *single* datatype-adaptive interface: int, float
and mixed formats behind one MAC contract, with runtime datatype switching.
Before this module the repro scattered that contract across four knobs —
per-leaf scheme strings (``get_scheme``), ad-hoc ``QuantMaker`` plan dicts,
``ServeConfig.kv_dtype``, and the process-global kernel toggles in
``kernels/ops.py``.  ``PrecisionPolicy`` consolidates them into one frozen,
JSON-serializable object:

  * ``weights`` — ordered (layer-name pattern, scheme) pairs, first match
    wins; patterns are ``fnmatch`` globs over the logical leaf names the
    Maker walk and the partitioning rules already share ("attn.wq",
    "ffn.*", "moe.w_up", ...).  An unmatched name keeps its config default.
  * ``kv``      — KV-cache storage tier: 'bf16' | 'int8' | 'fp8'.
  * ``kernel``  — execution dispatch: 'auto' (decided per site: under a
    multi-device mesh the kernels run shard_map'd; on one device a packed
    quantized weight's product — awq_int4 / mxfp4 / fp8 — runs the packed
    Pallas kernel where the backend compiles Pallas natively (a TPU) at up
    to ``kernels.ops.AUTO_KERNEL_MAX_ROWS`` rows, while decode attention,
    w8a8 and every CPU product take the jnp reference path), 'jnp' (force
    the reference path), 'pallas' (force the fused kernels, shard_map'd
    under a mesh).

Everything downstream derives from the policy instead of carrying its own
knob: ``QuantMaker`` consumes ``resolved_plan(cfg)``,
``runtime/partitioning.param_specs`` derives shardings from the same plan,
``ServeConfig(policy=...)`` carries it into the serving engine (legacy
``kv_dtype=`` / ``plan=`` arguments are thin adapters emitting the
equivalent policy, bit-identity pinned), and ``kernels/ops`` dispatches on
``kernel``.  Validation is EAGER: unknown scheme/kv/kernel names raise at
construction, and ``validate_for(cfg, mesh)`` raises config- and
mesh-incompatibilities (group sizes that do not divide a leaf's K,
quantized KV on MLA or a non-packable d_head, Pallas under partitioning,
and — with ``strict_tp=True`` — packed-K groupings the tp split would
force to replicate) at policy-resolution time instead of at first pool
build or first trace.

Per-request runtime switching: the ``kv`` field is the *tier* a request
may override (``Request.kv_policy``) — the serving engine keys its jitted
steps by ``(n_slots, capacity, tier)`` and the scheduler cohorts decode
batches per tier, so one engine serves bf16/fp8/int8-KV traffic
concurrently (the software analogue of the paper's runtime datatype
switch, at the granularity JAX can retrace: per cache tree, not per MAC).
"""
from __future__ import annotations

import dataclasses
import functools
import json
from fnmatch import fnmatchcase
from typing import Any, Dict, Mapping, Optional, Tuple

from .schemes import KV_SCHEMES, SCHEMES

KERNEL_MODES = ("auto", "jnp", "pallas")
KV_TIERS = ("bf16",) + tuple(sorted(KV_SCHEMES))


def _kv_tier_name(kv_dtype) -> str:
    """Canonical tier name for any legacy ``kv_dtype`` spelling (string
    name or the jnp.bfloat16 dtype), raising a ``ValueError`` with the
    valid tiers — the eager twin of ``quant.kv_cache.kv_dtype_name``.
    A non-bf16 raw dtype is rejected rather than silently coerced: tiers
    name the three supported storage formats, and an f32 pool (say) has
    different bytes and numerics than anything a tier could honor."""
    if kv_dtype is None:
        return "bf16"
    if not isinstance(kv_dtype, str):
        import jax.numpy as jnp
        if jnp.dtype(kv_dtype) == jnp.dtype(jnp.bfloat16):
            return "bf16"               # legacy jnp-dtype spelling
        raise ValueError(
            f"KV pool dtype {kv_dtype!r} is not expressible as a "
            f"precision tier; valid tiers: {list(KV_TIERS)} (raw-dtype "
            "slabs remain available via KVCachePool directly)")
    if kv_dtype not in KV_TIERS:
        raise ValueError(
            f"unknown KV tier {kv_dtype!r}; valid tiers: {list(KV_TIERS)}")
    return kv_dtype


@dataclasses.dataclass(frozen=True)
class PrecisionPolicy:
    """One declarative precision configuration, eagerly validated."""
    weights: Tuple[Tuple[str, str], ...] = ()
    kv: str = "bf16"
    kernel: str = "auto"

    def __post_init__(self):
        # accept a mapping or any iterable of pairs; store as tuple-of-
        # tuples so the policy is hashable (jit-cache keys) and frozen
        w = self.weights
        if isinstance(w, Mapping):
            w = tuple(w.items())
        w = tuple((str(p), str(s)) for p, s in w)
        object.__setattr__(self, "weights", w)
        for pat, scheme in w:
            if scheme not in SCHEMES:
                raise ValueError(
                    f"policy weights[{pat!r}]: unknown scheme {scheme!r}; "
                    f"valid schemes: {sorted(SCHEMES)}")
        object.__setattr__(self, "kv", _kv_tier_name(self.kv))
        if self.kernel not in KERNEL_MODES:
            raise ValueError(
                f"policy kernel={self.kernel!r}; valid modes: "
                f"{list(KERNEL_MODES)}")

    # ------------------------------------------------------------------
    # Resolution
    # ------------------------------------------------------------------
    def resolve(self, name: str, default: Optional[str] = None) -> str:
        """Scheme for logical leaf ``name``: first matching pattern wins,
        else the config default (None reads as dense 'bf16')."""
        for pat, scheme in self.weights:
            if fnmatchcase(name, pat):
                return scheme
        return default if default is not None else "bf16"

    def resolved_plan(self, cfg) -> Dict[str, str]:
        """The policy applied to ``cfg``: a concrete {leaf name -> scheme}
        map over every dense leaf of the model — the ``plan`` dict
        ``QuantMaker`` and ``partitioning.param_specs`` consume.  Leaves
        the policy does not match keep their config-default scheme."""
        return {name: self.resolve(name, default)
                for name, default in leaf_schemes(cfg).items()}

    # ------------------------------------------------------------------
    # Serialization (the policy is a deployment artifact).  The frozen
    # dataclass is itself hashable — jit-cache / cohort keys use the
    # policy's components directly (the serving engine keys steps by
    # (n_slots, capacity, tier)).
    # ------------------------------------------------------------------
    def to_dict(self) -> Dict[str, Any]:
        return {"weights": [list(p) for p in self.weights],
                "kv": self.kv, "kernel": self.kernel}

    def to_json(self, indent: Optional[int] = None) -> str:
        return json.dumps(self.to_dict(), indent=indent)

    @classmethod
    def from_dict(cls, d: Mapping[str, Any]) -> "PrecisionPolicy":
        unknown = set(d) - {"weights", "kv", "kernel"}
        if unknown:
            raise ValueError(
                f"policy dict has unknown keys {sorted(unknown)}; "
                "expected {'weights', 'kv', 'kernel'}")
        return cls(weights=tuple(tuple(p) for p in d.get("weights", ())),
                   kv=d.get("kv", "bf16"), kernel=d.get("kernel", "auto"))

    @classmethod
    def from_json(cls, s: str) -> "PrecisionPolicy":
        return cls.from_dict(json.loads(s))

    # ------------------------------------------------------------------
    # Legacy adapters (bit-identity pinned: the emitted policy resolves to
    # exactly the configuration the legacy knobs produced)
    # ------------------------------------------------------------------
    @classmethod
    def from_legacy(cls, *, kv_dtype=None,
                    plan: Optional[Mapping[str, str]] = None,
                    kernel: str = "auto") -> "PrecisionPolicy":
        """Adapter for the pre-policy knobs: a ``QuantMaker`` plan dict
        becomes exact-name weight patterns (a name with no glob characters
        only matches itself), ``kv_dtype`` becomes the tier."""
        return cls(weights=tuple((plan or {}).items()),
                   kv=_kv_tier_name(kv_dtype), kernel=kernel)

    def with_plan(self, plan: Mapping[str, str]) -> "PrecisionPolicy":
        """This policy with exact-name ``plan`` entries prepended (they
        win over the policy's own patterns, mirroring plan-over-config
        precedence of the legacy path)."""
        if not plan:
            return self
        return dataclasses.replace(
            self, weights=tuple(plan.items()) + self.weights)

    # ------------------------------------------------------------------
    # Eager validation against a model config (and optionally a mesh)
    # ------------------------------------------------------------------
    def validate_for(self, cfg, mesh=None, *,
                     strict_tp: bool = False) -> "PrecisionPolicy":
        """Raise every config/mesh incompatibility NOW, with an actionable
        message — not at first pool build or first trace.

        Checks: every weight pattern matches at least one leaf; every
        resolved quantized leaf's K is divisible by the scheme's packing
        word and scale group; a quantized KV tier needs a GQA cache with
        ``d_head % 4 == 0`` (MLA latents stay bf16, DESIGN.md §9);
        ``kernel='pallas'`` is accepted under a multi-device mesh (the
        kernels run shard_map'd, falling back to jnp per site).
        ``strict_tp=True`` additionally rejects policies whose packed-K
        grouping FORCES replication of a leaf the name rules would
        otherwise K-shard over the model axis (word/scale-group
        boundaries not aligned with the tp split) — useful when sharded
        memory capacity is part of the deployment contract.  By default
        such leaves replicate silently instead: the ``param_specs`` rules
        guarantee codes/scales shard in lockstep by construction
        (DESIGN.md §10), so misalignment costs memory, never
        correctness.  Returns self for chaining."""
        from .schemes import effective_group, get_scheme
        from .pack import codes_per_word

        info = leaf_info(cfg)
        for pat, _ in self.weights:
            if not any(fnmatchcase(n, pat) for n in info):
                raise ValueError(
                    f"policy weights pattern {pat!r} matches no leaf of "
                    f"{cfg.name!r}; leaves: {sorted(info)}")

        tp = int(mesh.shape.get("model", 1)) if mesh is not None else 1
        for name, (k, _, default) in info.items():
            scheme_name = self.resolve(name, default)
            if scheme_name == "bf16":
                continue
            s = get_scheme(scheme_name)
            group = effective_group(s.group_size, k)
            if k % group != 0:
                raise ValueError(
                    f"policy: leaf {name!r} has K={k}, not divisible by "
                    f"{scheme_name!r}'s scale group {s.group_size} — pick "
                    "a scheme whose group divides K (or keep the leaf "
                    "dense with 'bf16')")
            if s.packed and k % codes_per_word(s.weight_bits) != 0:
                raise ValueError(
                    f"policy: leaf {name!r} has K={k}, not packable "
                    f"{codes_per_word(s.weight_bits)}-per-int32-word for "
                    f"{scheme_name!r}")
            if strict_tp and tp > 1 and k % tp == 0 and k >= tp:
                shard = k // tp
                per_word = codes_per_word(s.weight_bits) if s.packed else 1
                if shard % group != 0 or shard % per_word != 0:
                    raise ValueError(
                        f"policy: leaf {name!r} K={k} at tp={tp} gives "
                        f"per-shard K={shard}, which splits "
                        f"{scheme_name!r}'s "
                        + (f"scale group {group}" if shard % group else
                           f"{per_word}-code packing word")
                        + " — the leaf would silently replicate; lower tp,"
                        " change the group size, or drop strict_tp")

        validate_kv_tier(self.kv, cfg)

        # kernel='pallas' is valid under a multi-device mesh: the kernels
        # run shard_map'd over it (DESIGN.md §14), with per-site fallback
        # where shard-local shapes cannot tile — no eager rejection.
        return self


def validate_kv_tier(tier, cfg=None) -> str:
    """Canonical tier name, eagerly validated (optionally against a model
    config) with an actionable message — the check the serving engine runs
    for every pool tier, including per-request overrides."""
    name = _kv_tier_name(tier)
    if cfg is not None and name != "bf16":
        if getattr(cfg, "use_mla", False):
            raise ValueError(
                f"kv tier {name!r}: KV quantization covers the GQA "
                "per-head cache; the MLA latent cache is already "
                "compressed and stays bf16 (DESIGN.md §9) — use 'bf16' "
                "for MLA models")
        if cfg.head_dim % 4 != 0:
            raise ValueError(
                f"kv tier {name!r}: d_head={cfg.head_dim} is not "
                "divisible by 4 (quantized KV packs 4 codes per int32 "
                "word along d_head) — use 'bf16'")
    return name


# ---------------------------------------------------------------------------
# Config walk (lazy model imports: quant is imported by the model layer)
# ---------------------------------------------------------------------------
@functools.lru_cache(maxsize=64)
def leaf_info(cfg) -> Dict[str, Tuple[int, int, str]]:
    """{logical dense-leaf name -> (K, N, config-default scheme)} for
    ``cfg`` — the name universe policies resolve against.  ONE abstract
    Maker walk (the same walk parameters and sharding rules use, so the
    three can't drift), cached per config: engine construction validates
    AND resolves against it without re-walking, and repeated engine
    builds over one config (tier pools, tests) pay nothing."""
    from repro.models.common import AbstractMaker
    from repro.models.transformer import build_params

    found: Dict[str, Tuple[int, int, str]] = {}

    class Probe(AbstractMaker):
        def __init__(self):
            super().__init__(quantize=False)

        def dense(self, name, stack, k, n, scheme=None):
            found[name] = (k, n, scheme if scheme is not None else "bf16")
            return super().dense(name, stack, k, n, scheme)

    build_params(cfg, Probe())
    return found


def leaf_schemes(cfg) -> Dict[str, str]:
    """{logical dense-leaf name -> config-default scheme} for ``cfg``."""
    return {name: s for name, (_, _, s) in leaf_info(cfg).items()}


def leaf_dims(cfg) -> Dict[str, Tuple[int, int]]:
    """{logical dense-leaf name -> (K, N)} for ``cfg``."""
    return {name: (k, n) for name, (k, n, _) in leaf_info(cfg).items()}
