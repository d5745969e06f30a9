"""Reduce a ``jax.profiler`` trace by the program's own measurement points.

The serving program names the parts of its step programs with
``jax.named_scope`` (``qmatmul``, ``attn_core``, ``kv_write``, ``lm_head``,
``sample``, inside ``attn`` / ``ffn`` / ``moe``) and marks the phases of a
scheduling round with host spans (``serve.step``, ``serve.admit``,
``serve.prefill``, ``serve.decode``, ``serve.sync``, ``serve.emit``).  From
the same ``*.xplane.pb`` that ``chipbench.trace.reduce`` reads, inside the
``bench.window`` span:

* scopes      per scope, the self seconds of the decode programs' device
              operations (``jit_decode_slots``, ``jit_decode_burst``) whose
              ``op_name`` path (the ``tf_op`` stat of the operation's
              metadata) has that scope innermost among the known ones;
              operations with none go to ``unattributed``
* ops_s       those operations' self seconds in all (the sum of ``scopes``)
* spans       per ``serve.*`` span of the host thread that runs the loop:
              count and summed seconds
* host_step   over the ``serve.step`` spans: count, and summed seconds less
              the ``serve.sync`` spans inside them (host work per round
              that the device waits behind)

Operations are read by their metadata id, never by their HLO text, which
repeats across programs.  ``jax.profiler.ProfileData`` exposes neither, so
the XSpace protobuf (``tsl/profiler/protobuf/xplane.proto``) is read from
its wire format here.  Self seconds are split as ``trace.self_times`` does,
and times are per chip, averaged over the chips.
"""
from __future__ import annotations

import collections
import struct
from typing import Dict, Iterator, List, Optional, Tuple

from . import records, trace as tr

SCOPES = ("qmatmul", "attn_core", "kv_write", "lm_head", "sample", "attn",
          "ffn", "moe")
DECODE = ("jit_decode_slots", "jit_decode_burst")
UNATTRIBUTED = "unattributed"


# --- the protobuf wire format -------------------------------------------
def _varint(buf, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf, i: int = 0, end: Optional[int] = None
            ) -> Iterator[Tuple[int, object]]:
    """(field number, value) of one message in ``buf[i:end]``: an int for
    varint and fixed-width fields, an (start, end) span for length-
    delimited ones (strings, bytes, nested messages)."""
    end = len(buf) if end is None else end
    while i < end:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            val, i = _varint(buf, i)
        elif kind == 2:
            n, i = _varint(buf, i)
            val, i = (i, i + n), i + n
        elif kind in (1, 5):
            w = 8 if kind == 1 else 4
            val, i = int.from_bytes(buf[i:i + w], "little"), i + w
        else:
            raise ValueError(f"wire type {kind} at byte {i}")
        yield key >> 3, val


def _str(buf, span) -> str:
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def _stats(buf, spans, stat_names) -> Dict[str, object]:
    """XStat messages -> {name: value}; a ``ref_value`` names a string
    through the plane's stat metadata."""
    out = {}
    for sp in spans:
        name = value = None
        for f, v in _fields(buf, *sp):
            if f == 1:
                name = stat_names.get(v)
            elif f == 5:
                value = _str(buf, v)
            elif f == 7:
                value = stat_names.get(v)
            elif f in (3, 4):
                value = v
            elif f == 2:
                value = struct.unpack("<d", v.to_bytes(8, "little"))[0]
        if name is not None:
            out[name] = value
    return out


def _plane(buf, span):
    """One XPlane: (name, {line name: [(start_ns, end_ns, metadata id,
    stat spans)]}, {metadata id: (name, stat spans)}, {stat id: name})."""
    name, lines, meta, stat_names = "", [], {}, {}
    for f, v in _fields(buf, *span):
        if f == 2:
            name = _str(buf, v)
        elif f == 3:
            lines.append(v)
        elif f in (4, 5):
            key, val = 0, None
            for g, w in _fields(buf, *v):
                if g == 1:
                    key = w
                elif g == 2:
                    val = w
            if val is None:
                continue
            if f == 5:
                for g, w in _fields(buf, *val):
                    if g == 2:
                        stat_names[key] = _str(buf, w)
            else:
                ev_name, ev_stats = "", []
                for g, w in _fields(buf, *val):
                    if g == 2:
                        ev_name = _str(buf, w)
                    elif g == 5:
                        ev_stats.append(w)
                meta[key] = (ev_name, ev_stats)
    out = {}
    for ln in lines:
        ln_name, t0, events = "", 0, []
        for f, v in _fields(buf, *ln):
            if f == 2:
                ln_name = _str(buf, v)
            elif f == 3:
                t0 = v
            elif f == 4:
                events.append(v)
        rows = []
        for ev in events:
            mid = off = dur = 0
            stats = []
            for f, v in _fields(buf, *ev):
                if f == 1:
                    mid = v
                elif f == 2:
                    off = v
                elif f == 3:
                    dur = v
                elif f == 4:
                    stats.append(v)
            # whole nanoseconds, as jax.profiler.ProfileData gives them
            start = (t0 * 1000 + off) // 1000
            rows.append((start, start + dur // 1000, mid, stats))
        out.setdefault(ln_name, []).extend(rows)
    return name, out, meta, stat_names


def read_space(path: str):
    """Every plane of the trace file, as ``_plane`` gives it."""
    with open(path, "rb") as f:
        buf = memoryview(f.read())
    return [_plane(buf, v) + (buf,) for f, v in _fields(buf) if f == 1]


# --- the reduction --------------------------------------------------------
def scope_of(op_path: Optional[str]) -> str:
    """The innermost known scope on an ``op_name`` path."""
    for part in reversed((op_path or "").split("/")):
        if part in SCOPES:
            return part
    return UNATTRIBUTED


def reduce(path: str, window: str = "bench.window") -> Optional[Dict]:
    """The numbers above from one trace file; None when it holds no window
    span or no device plane."""
    planes = read_space(path)
    host: List[Tuple[float, float, str, Dict]] = []
    devices = []
    for name, lines, meta, stat_names, buf in planes:
        if name.startswith("/host:"):
            for events in lines.values():
                named = [(s, e, meta.get(m, ("", []))[0], st)
                         for s, e, m, st in events]
                if any(n == window for _, _, n, _ in named):
                    host = [(s, e, n, _stats(buf, st, stat_names))
                            for s, e, n, st in named]
        elif tr._DEVICE.match(name) and lines.get("XLA Ops"):
            devices.append((lines, meta, stat_names, buf))
    spans = [h for h in host if h[2] == window]
    if not spans or not devices:
        return None
    w0, w1 = spans[0][0], spans[0][1]
    scopes: Dict[str, float] = collections.defaultdict(float)
    for lines, meta, stat_names, buf in devices:
        mods = sorted((s, e, meta.get(m, ("", []))[0].split("(", 1)[0])
                      for s, e, m, _ in lines.get("XLA Modules", []))
        starts = [m[0] for m in mods]
        scope_by_id: Dict[int, str] = {}
        inside = []
        for s, e, mid, st in lines["XLA Ops"]:
            if not (w0 <= s and e <= w1):
                continue
            if mid not in scope_by_id:
                stats = _stats(buf, meta.get(mid, ("", []))[1], stat_names)
                stats.update(_stats(buf, st, stat_names))
                scope_by_id[mid] = scope_of(stats.get("tf_op"))
            prog = tr.module_of(s, mods, starts)
            inside.append((s, e, prog + "/" + scope_by_id[mid]))
        for key, t in tr.self_times(inside).items():
            prog, scope = key.rsplit("/", 1)
            if prog.startswith(DECODE):
                scopes[scope] += t / 1e9
    n = len(devices)
    scopes = {k: v / n for k, v in scopes.items()}
    in_window = [h for h in host if h[2].startswith("serve.")
                 and w0 <= h[0] and h[1] <= w1]
    by_name: Dict[str, List[float]] = {}
    for s, e, name, _ in in_window:
        c = by_name.setdefault(name, [0, 0.0])
        c[0] += 1
        c[1] += (e - s) / 1e9
    syncs = [(s, e) for s, e, name, _ in in_window if name == "serve.sync"]
    steps = [(s, e) for s, e, name, _ in in_window if name == "serve.step"]
    host_s = sum((e - s) - sum(b - a for a, b in syncs if s <= a and b <= e)
                 for s, e in steps) / 1e9
    return {"scopes": scopes, "ops_s": sum(scopes.values()),
            "spans": {k: {"count": v[0], "seconds": v[1]}
                      for k, v in by_name.items()},
            "host_step": {"count": len(steps), "seconds": host_s}}


def ms_per_token_step(rec: Dict, scope: str) -> Optional[float]:
    """A scope's device seconds in the decode programs over the decode
    token-steps of the window (``rec``: a run's record), in ms; None when
    the trace holds no such scope."""
    secs = (rec.get("scopes") or {}).get("scopes", {}).get(scope)
    token_steps = sum(s["token_steps"] for s in records.window_steps(rec))
    if not secs or not token_steps:
        return None
    return 1e3 * secs / token_steps


def table(red: Optional[Dict]) -> str:
    """The per-scope split as lines for the run's log."""
    if not red:
        return "scopes: no window in the trace"
    total = red["ops_s"] or 1.0
    rows = [f"scopes of the decode programs ({red['ops_s']!r} s of ops):"]
    for k, v in sorted(red["scopes"].items(), key=lambda kv: -kv[1]):
        rows.append(f"  {k:<14} {v!r} s ({100 * v / total:.2f}%)")
    for k, v in sorted(red["spans"].items()):
        rows.append(f"  span {k:<14} x{v['count']} {v['seconds']!r} s")
    h = red["host_step"]
    rows.append(f"  host work in serve.step (less serve.sync): "
                f"x{h['count']} {h['seconds']!r} s")
    return "\n".join(rows)
