"""Reduce a ``jax.profiler`` trace to the numbers the benchmark reports.

The run wraps its measured window in a host span ``bench.window`` and its
own calls in ``bench.<what>`` spans (``jax.profiler.TraceAnnotation``); the
profiler puts those host spans and the device's events on one clock.  From
the trace (``*.xplane.pb``, read with ``jax.profiler.ProfileData``):

* window_s    length of the ``bench.window`` span
* busy_s      union of the intervals in which an operation ran on the
              device (line ``XLA Ops`` of each ``/device:TPU:<n>`` plane),
              clipped to the window, averaged over the chips
* programs    per jitted program (line ``XLA Modules``, name up to its
              ``(``): summed device seconds and executions in the window
* ops         per operation, named ``<program>/<op>``: summed self seconds
              (an operation's time less the operations nested inside it)
* idle        the device's idle intervals in the window, summed by what the
              host was doing at each one's midpoint: the innermost
              ``bench.*`` span there, and the shortest other event of the
              host thread that runs the loop (the line holding the window)
"""
from __future__ import annotations

import bisect
import collections
import glob
import os
import re
from typing import Dict, List, Optional, Tuple

_DEVICE = re.compile(r"^/device:TPU:\d+$")


def find_xplane(trace_dir: str) -> str:
    found = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(found, key=os.path.getmtime)


def union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo: float, hi: float):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def op_name(full: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion.12``."""
    head = full.split(" = ", 1)[0].strip()
    return head[1:] if head.startswith("%") else head


def module_of(t: float, mods, starts) -> str:
    """Name of the program (``XLA Modules`` event) running at ``t``."""
    i = bisect.bisect_right(starts, t) - 1
    return mods[i][2] if i >= 0 and mods[i][1] >= t else "?"


def self_times(events: List[Tuple[float, float, str]]) -> Dict[str, float]:
    """Per name, summed duration less that of directly nested events."""
    out: Dict[str, float] = collections.defaultdict(float)
    stack: List[Tuple[float, str]] = []
    for s, e, name in sorted(events, key=lambda x: (x[0], -(x[1] - x[0]))):
        while stack and stack[-1][0] <= s:
            stack.pop()
        if stack:
            out[stack[-1][1]] -= e - s
        out[name] += e - s
        stack.append((e, name))
    return dict(out)


def _events(line):
    return [(e.start_ns, e.end_ns, e.name) for e in line.events]


def reduce(path: str, window: str = "bench.window") -> Optional[Dict]:
    """The numbers above from one trace file; None when the trace holds no
    window span or no device plane."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    host: List[Tuple[float, float, str]] = []
    devices = []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                evs = _events(line)
                if any(h[2] == window for h in evs):   # the loop's thread
                    host = evs
        elif _DEVICE.match(plane.name):
            lines = {ln.name: _events(ln) for ln in plane.lines}
            if lines.get("XLA Ops"):
                devices.append(lines)
    spans = [h for h in host if h[2] == window]
    if not spans or not devices:
        return None
    w0, w1 = spans[0][0], spans[0][1]
    busy, programs, ops = [], {}, collections.defaultdict(float)
    idle_by: Dict[str, List[float]] = collections.defaultdict(lambda: [0, 0])
    marks = [h for h in host if h[2].startswith("bench.") and h[2] != window]
    others = [h for h in host if not h[2].startswith("bench.")]
    for lines in devices:
        op_iv = clip([(s, e) for s, e, _ in lines["XLA Ops"]], w0, w1)
        covered = union(op_iv)
        busy.append(sum(e - s for s, e in covered))
        for s, e, name in lines.get("XLA Modules", []):
            if w0 <= s and e <= w1:
                p = programs.setdefault(name.split("(", 1)[0], [0.0, 0])
                p[0] += (e - s) / 1e9
                p[1] += 1
        mods = sorted((s, e, n.split("(", 1)[0])
                      for s, e, n in lines.get("XLA Modules", []))
        starts = [m[0] for m in mods]
        inside = [(s, e, module_of(s, mods, starts) + "/" + op_name(n))
                  for s, e, n in lines["XLA Ops"] if w0 <= s and e <= w1]
        for name, t in self_times(inside).items():
            ops[name] += t / 1e9
        edges = [w0] + [x for iv in covered for x in iv] + [w1]
        gaps = [(s, e) for s, e in zip(edges[::2], edges[1::2]) if e > s]
        for (s, e), lab in zip(gaps, labels([(s + e) / 2 for s, e in gaps],
                                            marks, others)):
            idle_by[lab][0] += (e - s) / 1e9
            idle_by[lab][1] += 1
    n = len(devices)
    return {
        "window_s": (w1 - w0) / 1e9,
        "busy_s": sum(busy) / n / 1e9,
        "n_devices": n,
        "programs": {k: {"seconds": v[0] / n, "count": v[1] / n}
                     for k, v in programs.items()},
        "ops": {k: v / n for k, v in ops.items()},
        "idle": {k: {"seconds": v[0] / n, "count": v[1] / n}
                 for k, v in idle_by.items()},
    }


def _innermost(times: List[float], events) -> List[Optional[str]]:
    """For each of the ascending ``times``, the shortest event covering it
    (a sweep: events are added as they start and dropped once ended)."""
    events = sorted(events)
    out, active, i = [], [], 0
    for t in times:
        while i < len(events) and events[i][0] <= t:
            active.append(events[i])
            i += 1
        active = [h for h in active if h[1] >= t]
        out.append(min(active, key=lambda h: h[1] - h[0])[2]
                   if active else None)
    return out


def labels(times: List[float], marks, others) -> List[str]:
    """What the host was doing at each of the ascending ``times``: the
    innermost ``bench.*`` span and the shortest other host event there."""
    out = []
    for mark, other in zip(_innermost(times, marks),
                           _innermost(times, others)):
        mark = (mark or "bench.none")[len("bench."):]
        out.append(mark + (f":{other}" if other else ""))
    return out


def program_seconds(red: Dict, prefixes) -> Tuple[float, float]:
    """(device seconds, executions) of the programs whose names start with
    one of ``prefixes`` (``jit_decode_burst`` covers every burst length)."""
    t = c = 0.0
    for name, v in red["programs"].items():
        if any(name.startswith(p) for p in prefixes):
            t += v["seconds"]
            c += v["count"]
    return t, c


def breakdown(red: Dict, top: int = 10) -> Dict[str, List]:
    """The ``breakdown`` of a result line: the device operations that took
    most time, and the longest idle time by what the host was doing."""
    ops = sorted(red["ops"].items(), key=lambda kv: -kv[1])[:top]
    idle = sorted(red["idle"].items(), key=lambda kv: -kv[1]["seconds"])
    return {"device_ops": [[k, v] for k, v in ops],
            "idle_gaps": [[f"{k} (x{v['count']:g})", v["seconds"]]
                          for k, v in idle[:top]]}
