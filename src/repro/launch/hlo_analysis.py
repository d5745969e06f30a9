"""Trip-count-aware cost analysis of compiled (post-SPMD) HLO text.

XLA's ``compiled.cost_analysis()`` counts while-loop bodies ONCE — under a
scan-over-layers schedule that understates FLOPs/bytes by the layer count.
This module parses the compiled HLO module text and walks the call graph
from ENTRY, multiplying each while body by its ``known_trip_count``
(emitted by XLA in ``backend_config``), giving:

  * flops              — 2 * prod(result dims) * prod(contracting dims)
                         summed over every dot (fusion-nested dots included)
  * hbm_bytes          — per-instruction operand+result bytes at fusion
                         granularity (fusions are the HBM-traffic unit;
                         intra-fusion values never hit HBM)
  * collective bytes   — per collective type, result-shape bytes
                         (reduce-scatter scaled by group size = operand)

All numbers are PER DEVICE (the HLO is the per-device SPMD program).
Validated against hand-computed matmul programs in tests/test_hlo_analysis.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, List, Optional, Tuple

_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2,
    "s32": 4, "u32": 4, "s64": 8, "u64": 8, "f16": 2, "bf16": 2, "f32": 4,
    "f64": 8, "f8e4m3fn": 1, "f8e5m2": 1, "f8e4m3": 1, "c64": 8, "c128": 16,
}

COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
               "collective-permute")

_SKIP_BYTES = {"parameter", "constant", "tuple", "get-tuple-element",
               "bitcast", "after-all", "partition-id", "replica-id", "iota",
               "while", "conditional", "call"}

_shape_re = re.compile(r"(\w+)\[([\d,]*)\]")


def _shapes_in(type_str: str) -> List[Tuple[str, List[int]]]:
    out = []
    for dt, dims in _shape_re.findall(type_str):
        if dt in _DTYPE_BYTES:
            out.append((dt, [int(d) for d in dims.split(",") if d]))
    return out


def _bytes_of(type_str: str) -> int:
    total = 0
    for dt, dims in _shapes_in(type_str):
        n = 1
        for d in dims:
            n *= d
        total += n * _DTYPE_BYTES[dt]
    return total


@dataclasses.dataclass
class Instr:
    name: str
    type_str: str
    op: str
    operands: List[str]
    line: str


@dataclasses.dataclass
class Computation:
    name: str
    instrs: List[Instr]
    symtab: Dict[str, str]          # value name -> type string


_header_re = re.compile(r"^(?:ENTRY\s+)?%?([^\s(]+)\s*\(.*\{\s*$")
_instr_re = re.compile(r"^\s+(?:ROOT\s+)?%?([^\s=]+)\s*=\s*(.*)$")


def _split_type_op(rest: str) -> Optional[Tuple[str, str, str]]:
    """'(f32[],..) while(%t), attrs' -> (type_str, op, tail)."""
    rest = rest.strip()
    if rest.startswith("("):
        depth = 0
        for i, ch in enumerate(rest):
            depth += ch == "("
            depth -= ch == ")"
            if depth == 0:
                type_str, tail = rest[: i + 1], rest[i + 1:]
                break
        else:
            return None
    else:
        sp = rest.find(" ")
        if sp < 0:
            return None
        type_str, tail = rest[:sp], rest[sp:]
    m = re.match(r"\s*([\w\-]+)\((.*)$", tail, re.S)
    if not m:
        return None
    return type_str, m.group(1), m.group(2)


def parse_module(text: str) -> Dict[str, Computation]:
    comps: Dict[str, Computation] = {}
    cur: Optional[Computation] = None
    entry_name = None
    for line in text.splitlines():
        if cur is None:
            if line.rstrip().endswith("{") and ("(" in line) and "->" in line:
                m = _header_re.match(line.strip())
                if m:
                    cur = Computation(m.group(1), [], {})
                    if line.lstrip().startswith("ENTRY"):
                        entry_name = m.group(1)
            continue
        if line.startswith("}") or line.strip() == "}":
            comps[cur.name] = cur
            cur = None
            continue
        m = _instr_re.match(line)
        if not m:
            continue
        name, rest = m.groups()
        sto = _split_type_op(rest)
        if sto is None:
            continue
        type_str, op, tail = sto
        # first-level operand names
        depth, ops_str = 0, []
        for ch in tail:
            if ch == "(":
                depth += 1
            elif ch == ")":
                if depth == 0:
                    break
                depth -= 1
            ops_str.append(ch)
        operands = re.findall(r"%([\w\.\-]+)", "".join(ops_str))
        instr = Instr(name, type_str, op, operands, line)
        cur.instrs.append(instr)
        cur.symtab[name] = type_str
    if entry_name:
        comps["__entry__"] = comps[entry_name]
    return comps


def _dot_flops(instr: Instr, symtab: Dict[str, str]) -> float:
    out_elems = 1
    for _, dims in _shapes_in(instr.type_str):
        for d in dims:
            out_elems *= d
    m = re.search(r"lhs_contracting_dims=\{([\d,]*)\}", instr.line)
    cdims = [int(x) for x in m.group(1).split(",") if x] if m else []
    lhs_type = symtab.get(instr.operands[0]) if instr.operands else None
    contract = 1
    if lhs_type:
        shapes = _shapes_in(lhs_type)
        if shapes:
            dims = shapes[0][1]
            for c in cdims:
                if c < len(dims):
                    contract *= dims[c]
    return 2.0 * out_elems * contract


def _group_size(line: str) -> int:
    m = re.search(r"replica_groups=\[(\d+),(\d+)\]", line)
    if m:
        return int(m.group(2))
    m = re.search(r"replica_groups=\{\{([\d,]+)\}", line)
    if m:
        return len(m.group(1).split(","))
    return 1


@dataclasses.dataclass
class Cost:
    flops: float = 0.0
    hbm_bytes: float = 0.0
    transcendentals: float = 0.0
    coll: Dict[str, Dict[str, float]] = dataclasses.field(
        default_factory=lambda: {k: {"count": 0, "bytes": 0.0}
                                 for k in COLLECTIVES})

    def add(self, o: "Cost", mult: float = 1.0):
        self.flops += o.flops * mult
        self.hbm_bytes += o.hbm_bytes * mult
        self.transcendentals += o.transcendentals * mult
        for k in COLLECTIVES:
            self.coll[k]["count"] += o.coll[k]["count"] * mult
            self.coll[k]["bytes"] += o.coll[k]["bytes"] * mult

    @property
    def collective_bytes(self) -> float:
        return sum(v["bytes"] for v in self.coll.values())

    def to_json(self):
        return {"flops": self.flops, "hbm_bytes": self.hbm_bytes,
                "transcendentals": self.transcendentals,
                "collective_bytes": self.collective_bytes,
                "collectives": self.coll}


_TRANSCENDENTAL_FUSION_HINT = re.compile(
    r"exponential|tanh|log|rsqrt|power|sine|cosine")

# ops whose real HBM traffic is the ACCESSED REGION, not the whole operand:
#  dynamic-slice / gather read ~result-sized regions of a large buffer
#  (scan xs slicing, embedding lookups); dynamic-update-slice writes the
#  update region in place (donated caches).  Counting full operands would
#  scale scan-sliced stacks by the trip count — an L x overstatement.
_REGION_OPS = {"dynamic-slice", "gather", "dynamic-update-slice"}


_PASSTHRU = {"bitcast", "reshape", "copy", "transpose", "convert"}
_SLICERS = {"dynamic-slice", "slice", "gather"}


def _param_traffic(callee: Computation, param_idx: int, full_bytes: float
                   ) -> float:
    """Traffic a fusion really does on operand ``param_idx``: if the callee
    only SLICES that parameter (scan xs / cache reads), the traffic is the
    slice size, not the whole buffer — otherwise the loop trip count would
    multiply the full stacked array (an L x - 1000 x overstatement)."""
    pnames = [i.name for i in callee.instrs if i.op == "parameter"
              and re.search(rf"parameter\({param_idx}\)", i.line)]
    if not pnames:
        return full_bytes
    frontier = set(pnames)
    consumers: List[Instr] = []
    for ins in callee.instrs:
        if any(o in frontier for o in ins.operands):
            if ins.op in _PASSTHRU:
                frontier.add(ins.name)
            else:
                consumers.append(ins)
    if consumers and all(c.op in _SLICERS for c in consumers):
        return sum(_bytes_of(c.type_str) for c in consumers)
    if consumers and all(c.op == "dynamic-update-slice" for c in consumers):
        # in-place write of an update region into the big buffer
        upd = [callee.symtab.get(c.operands[1]) for c in consumers
               if len(c.operands) > 1]
        return sum(_bytes_of(u) for u in upd if u)
    return full_bytes


def _result_traffic(ins: Instr, callee: Optional[Computation]) -> float:
    """Result-side traffic; a fusion rooted at dynamic-update-slice writes
    only the update region (output aliases the input buffer)."""
    full = _bytes_of(ins.type_str)
    if callee is None:
        return full
    roots = [i for i in callee.instrs if i.line.lstrip().startswith("ROOT")]
    if len(roots) == 1 and roots[0].op == "dynamic-update-slice":
        upd = callee.symtab.get(roots[0].operands[1]) \
            if len(roots[0].operands) > 1 else None
        if upd:
            return _bytes_of(upd)
    return full


def _instr_bytes(ins: Instr, symtab: Dict[str, str],
                 comps: Optional[Dict[str, Computation]] = None) -> float:
    if ins.op in _REGION_OPS:
        if ins.op == "dynamic-update-slice":
            upd = symtab.get(ins.operands[1]) if len(ins.operands) > 1 else None
            return 2.0 * _bytes_of(upd) if upd else 0.0
        return 2.0 * _bytes_of(ins.type_str)       # read region + write result
    callee = None
    if comps is not None and ins.op in ("fusion", "custom-call"):
        m = re.search(r"calls=%?([\w\.\-]+)", ins.line)
        if m:
            callee = comps.get(m.group(1))
    nbytes = _result_traffic(ins, callee)
    for idx, opnd in enumerate(ins.operands):
        t = symtab.get(opnd)
        if t is None:
            continue
        full = _bytes_of(t)
        nbytes += _param_traffic(callee, idx, full) if callee else full
    return nbytes


def _flops_only(comp: Computation, comps, memo_f) -> Cost:
    """flops + collectives of a computation INCLUDING nested fusions."""
    if comp.name in memo_f:
        return memo_f[comp.name]
    c = Cost()
    for ins in comp.instrs:
        if ins.op == "dot":
            c.flops += _dot_flops(ins, comp.symtab)
        elif ins.op in COLLECTIVES:
            nbytes = _bytes_of(ins.type_str)
            if ins.op == "reduce-scatter":
                nbytes *= _group_size(ins.line)
            c.coll[ins.op]["count"] += 1
            c.coll[ins.op]["bytes"] += nbytes
        callee = re.search(r"(?:calls|to_apply)=%?([\w\.\-]+)", ins.line)
        if callee and ins.op in ("fusion", "call", "custom-call"):
            sub = comps.get(callee.group(1))
            if sub is not None:
                c.add(_flops_only(sub, comps, memo_f))
    memo_f[comp.name] = c
    return c


def _cost_of(comp: Computation, comps, memo, memo_f) -> Cost:
    if comp.name in memo:
        return memo[comp.name]
    c = Cost()
    for ins in comp.instrs:
        if ins.op == "while":
            trip = 1
            m = re.search(r"known_trip_count[^0-9]*(\d+)", ins.line)
            if m:
                trip = int(m.group(1))
            body = re.search(r"body=%?([\w\.\-]+)", ins.line)
            cond = re.search(r"condition=%?([\w\.\-]+)", ins.line)
            if body and body.group(1) in comps:
                c.add(_cost_of(comps[body.group(1)], comps, memo, memo_f), trip)
            if cond and cond.group(1) in comps:
                c.add(_cost_of(comps[cond.group(1)], comps, memo, memo_f), trip)
            continue
        if ins.op == "conditional":
            branches = re.findall(r"%([\w\.\-]+)", ins.line.split("branch")[-1])
            sub = [ _cost_of(comps[b], comps, memo, memo_f)
                    for b in branches if b in comps]
            if sub:
                best = max(sub, key=lambda s: s.flops + s.hbm_bytes)
                c.add(best)
            continue
        if ins.op == "call":
            callee = re.search(r"to_apply=%?([\w\.\-]+)", ins.line)
            if callee and callee.group(1) in comps:
                c.add(_cost_of(comps[callee.group(1)], comps, memo, memo_f))
            continue
        if ins.op == "dot":
            c.flops += _dot_flops(ins, comp.symtab)
        elif ins.op in COLLECTIVES:
            nbytes = _bytes_of(ins.type_str)
            if ins.op == "reduce-scatter":
                nbytes *= _group_size(ins.line)
            c.coll[ins.op]["count"] += 1
            c.coll[ins.op]["bytes"] += nbytes
        elif ins.op in ("fusion", "custom-call"):
            callee = re.search(r"calls=%?([\w\.\-]+)", ins.line)
            if callee and callee.group(1) in comps:
                sub = _flops_only(comps[callee.group(1)], comps, memo_f)
                c.flops += sub.flops
                for k in COLLECTIVES:
                    c.coll[k]["count"] += sub.coll[k]["count"]
                    c.coll[k]["bytes"] += sub.coll[k]["bytes"]
            if _TRANSCENDENTAL_FUSION_HINT.search(ins.line):
                c.transcendentals += _bytes_of(ins.type_str) / 4.0
        # HBM bytes: fusion-granularity operand + result traffic
        if ins.op not in _SKIP_BYTES:
            c.hbm_bytes += _instr_bytes(ins, comp.symtab, comps)
    memo[comp.name] = c
    return c


def analyze(hlo_text: str) -> Cost:
    comps = parse_module(hlo_text)
    entry = comps.get("__entry__")
    if entry is None:
        return Cost()
    return _cost_of(entry, comps, {}, {})


def top_bytes_contributors(hlo_text: str, n: int = 25):
    """Debug: (bytes*trip, op, comp, shape-str) for the heaviest instrs."""
    comps = parse_module(hlo_text)
    entry = comps.get("__entry__")
    rows = []

    def visit(comp, mult):
        for ins in comp.instrs:
            if ins.op == "while":
                trip = 1
                m = re.search(r"known_trip_count[^0-9]*(\d+)", ins.line)
                if m:
                    trip = int(m.group(1))
                body = re.search(r"body=%?([\w\.\-]+)", ins.line)
                if body and body.group(1) in comps:
                    visit(comps[body.group(1)], mult * trip)
                continue
            if ins.op in _SKIP_BYTES:
                continue
            nb = _instr_bytes(ins, comp.symtab, comps)
            rows.append((nb * mult, ins.op, comp.name, ins.type_str[:60]))

    if entry is not None:
        visit(entry, 1)
    rows.sort(reverse=True)
    return rows[:n]


def compiled_step_cost(engine, pool, k: int = 1) -> Dict:
    """Trip-count-aware FLOP/byte counts of the COMPILED decode step for
    ``pool``'s geometry (``analyze`` over the post-optimization HLO text):
    what the program does per dispatch, independent of how long the host
    took.

    ``k > 1`` analyzes the K-step burst scan (the scan body is multiplied
    by its known trip count).  This lowers and compiles the step outside
    the engine's jit cache, so it is an offline/diagnostic call, not a
    hot-path one.
    """
    import jax
    import jax.numpy as jnp

    n = pool.n_slots
    f32 = jnp.float32

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype)

    cache = jax.tree_util.tree_map(
        lambda a: spec(a.shape, a.dtype), pool.cache)
    row_i32 = spec((n,), jnp.int32)
    operands = [spec(a.shape, a.dtype) for a in pool.step_operands()]
    if k <= 1:
        lowered = jax.jit(engine._decode_slots_fn).lower(
            engine.params, spec((n, 1), jnp.int32), cache, row_i32,
            spec((n, 2), jnp.uint32), spec((n,), f32), *operands)
    else:
        lowered = jax.jit(engine._decode_burst_fn).lower(
            engine.params, cache, row_i32, row_i32, spec((n,), jnp.bool_),
            row_i32, spec((k, n, 2), jnp.uint32), spec((n,), f32), row_i32,
            jnp.int32(pool.max_len), *operands)
    cost = analyze(lowered.compile().as_text())
    steps = k * n
    return {"k": k, "n_slots": n, "kv_dtype": pool.kv_dtype,
            "paged": pool.paged,
            **({"n_pages": pool.n_pages} if pool.paged else {}),
            "flops": cost.flops, "hbm_bytes": cost.hbm_bytes,
            "collective_bytes": cost.collective_bytes,
            "flops_per_token_step": round(cost.flops / steps, 1),
            "hbm_bytes_per_token_step": round(cost.hbm_bytes / steps, 1)}
