"""One run of one cell: set-up, the measured window, the readings, the check.

``run_cell`` drives the program's normal serving path — ``Scheduler`` over a
``ServingEngine`` and its KV pool, with the weights drawn from the seed — and
returns the result line's object.  It never looks for a chip itself:
``run.py`` does that before calling it, so tests can drive a whole run on
the CPU at a small configuration.

The traffic is a closed loop: one client per slot, each sending its next
request when the last one finishes, from a first fill made in set-up.
Timing is taken on the host, from the client's side: every token is stamped
when the ``Scheduler.step()`` that delivers it returns.
"""
from __future__ import annotations

import gc
import shutil
import sys
import tempfile
import time
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

from . import check, scopes, spec, trace as tr
from .traffic import Traffic


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


class CompileCounter:
    """Counts compilations (and compile-cache loads) while armed."""

    def __init__(self):
        import jax.monitoring as mon
        self.armed = False
        self.count = 0
        mon.register_event_listener(self._event)
        mon.register_event_duration_secs_listener(self._duration)

    def close(self) -> None:
        import jax.monitoring as mon
        mon.unregister_event_listener(self._event)
        mon.unregister_event_duration_listener(self._duration)

    def _event(self, name, **kw):
        if self.armed and name == "/jax/compilation_cache/" \
                "compile_requests_use_cache":
            self.count += 1

    def _duration(self, name, secs, **kw):
        if self.armed and name == "/jax/core/compile/backend_compile_duration":
            self.count += 1


class Served:
    """What the client saw of one request."""

    def __init__(self, req, sent: float):
        self.req = req
        self.sent = sent          # when it was submitted
        self.deliveries: List[tuple] = []   # (time, n tokens)
        self.n_delivered = 0


def annotate(name: str):
    import jax
    return jax.profiler.TraceAnnotation("bench." + name)


def warm_up(engine, pool, mix, max_burst: int) -> None:
    """Compile every program the cell's traffic drives, at the cell's pool
    geometry: the prefill chunk (final and non-final), the single decode
    step, and each power-of-two burst up to the program's ``max_burst``."""
    from repro.serve import Request, SamplingParams, Scheduler
    sched = Scheduler(engine, pool=pool)
    c = engine.scfg.prefill_chunk
    top = min(max_burst, mix["output"]["max"] - 1)
    k = 1
    while k <= top:
        sched.submit(Request(prompt=np.arange(1, 2 * c + 1, dtype=np.int32),
                             sampling=SamplingParams(max_new_tokens=k + 1)))
        sched.run(max_steps=100)
        k *= 2


def start_trace(trace_dir: str) -> None:
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(trace_dir, profiler_options=opts)


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             root: str = spec.ROOT, bench_dir: str = spec.BENCH_DIR,
             mutate: Optional[Callable] = None,
             t_start: Optional[float] = None,
             controls: tuple = (), trace_dir: Optional[str] = None
             ) -> Tuple[Dict, Dict]:
    """One run; returns the result line's object and the run's record,
    which the per-layer readers read.  ``mutate(engine)``, when given, is
    applied to the engine before warm-up (``chipbench.faults`` break the
    timed path with it); ``controls`` (``calibrate.py``) are lower
    precisions of the reference whose readings are added to the result
    beside the program's.  A traced run writes its trace under
    ``trace_dir`` and leaves it there (the record's ``trace_file``), or,
    when None, in a temporary directory that it removes."""
    t_start = time.perf_counter() if t_start is None else t_start
    import jax
    from repro.serve import Request, SamplingParams, Scheduler, ServingEngine

    from . import weights

    w = spec.workload(workload, root)
    cfg_file = spec.load_config(w["config"], bench_dir)
    mix = spec.load_traffic(w["traffic"], bench_dir)
    limits = spec.load_limits(workload, bench_dir)
    cfg = spec.model_config(cfg_file)
    traffic = Traffic(mix, seed, cfg.vocab)
    counter = CompileCounter()

    params = weights.make_params(cfg_file, cfg, seed, bench_dir)
    jax.block_until_ready(params)
    engine = ServingEngine(cfg, params, spec.serve_config(
        cfg_file, max_len=traffic.max_len))
    pool = engine.new_pool()
    if mutate is not None:
        mutate(engine)
    warm_up(engine, pool, mix, engine.scfg.max_burst)
    sched = Scheduler(engine, pool=pool)
    served: Dict[int, Served] = {}
    steps: List[Dict] = []
    clock = time.perf_counter

    def submit(prompt, max_new):
        with annotate("submit"):
            req = sched.submit(Request(prompt=prompt, sampling=SamplingParams(
                max_new_tokens=max_new)))
        served[id(req)] = Served(req, clock())
        return req

    def step(t0):
        """One ``Scheduler.step()``; each client whose request finished
        sends its next one."""
        ts0 = sched.metrics.decode_token_steps
        with annotate("step"):
            out = sched.step()
        t = clock()
        by_req: Dict[int, int] = {}
        for req, _, _ in out["emitted"]:
            by_req[id(req)] = by_req.get(id(req), 0) + 1
        rows = []
        for rid, n in by_req.items():
            s = served[rid]
            rows.append((s.req.prompt_len, s.n_delivered, n))
            s.deliveries.append((t, n))
            s.n_delivered += n
        # rows: (prompt length, tokens delivered before, tokens now) per
        # request; token j >= 1 of a request is a decode token that
        # attended prompt length + j positions
        steps.append({"t": t - t0, "rows": rows, "token_steps":
                      sched.metrics.decode_token_steps - ts0})
        for _ in out["finished"]:
            submit(*traffic.next())

    keep_trace = trace_dir is not None
    if trace and not keep_trace:
        trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-")
    # the first fill, until every client's request is decoding
    t_fill = clock()
    for prompt, max_new in traffic.fill(pool.n_slots):
        submit(prompt, max_new)
    while sched.waiting or any(r.state.value == "prefill"
                               for r in sched.running.values()):
        step(t_fill)
    fill_s = clock() - t_fill
    steps.clear()
    counter.armed = True
    if trace:
        start_trace(trace_dir)
    window = annotate("window")
    window.__enter__()
    t0 = clock()
    setup_s = t0 - t_start
    while clock() - t0 < seconds:
        step(t0)
    t_end = clock()
    # prompt tokens already in the cache of requests still in prefill
    partial = [(s.req.prompt_len, s.req.prefill_pos) for s in served.values()
               if s.req.state.value == "prefill" and s.sent >= t0]
    window.__exit__(None, None, None)
    if trace:
        jax.profiler.stop_trace()
    counter.armed = False
    counter.close()
    window_s = t_end - t0
    compiles = counter.count
    dev = jax.devices()[0]
    mem = (dev.memory_stats() or {}).get("peak_bytes_in_use")

    rec = {"workload": workload, "n_slots": pool.n_slots, "t0": t0,
           "t_end": t_end, "window_s": window_s, "steps": steps,
           "served": served, "cfg_file": cfg_file, "bench_dir": bench_dir,
           "max_len": traffic.max_len, "device_kind": dev.device_kind,
           "seconds": seconds, "prefill_partial": partial}
    log(f"cell {workload}: seed {seed}, {pool.n_slots} slots x "
        f"{traffic.max_len} positions, window {window_s!r} s")
    log(f"compilations inside the window: {compiles}")
    log(f"first fill of the slots (set-up): {fill_s!r} s")
    log(f"peak HBM in use: {mem!r} bytes")
    log(f"set-up: {setup_s!r} s")

    red = None
    if trace:
        try:
            path = tr.find_xplane(trace_dir)
            t = clock()
            red = tr.reduce(path)
            t_mid = clock()
            rec["scopes"] = scopes.reduce(path)
            log(f"trace reduced: by module in {t_mid - t!r} s, by scope "
                f"and span in {clock() - t_mid!r} s")
        finally:
            if not keep_trace:
                shutil.rmtree(trace_dir, ignore_errors=True)
        rec["trace"] = red
        if keep_trace:
            rec["trace_file"] = path
        log(scopes.table(rec["scopes"]))
    finished = [s for s in served.values() if s.req.is_finished]
    attempted = [s for s in served.values() if s.sent <= t_end]
    failed = [s for s in finished if s.req.finish_reason != "length"]

    # free the program's state before the reference runs
    del sched, pool, engine, params
    gc.collect()
    verdict = check.compare(cfg_file, seed, finished, limits,
                            max_len=traffic.max_len, bench_dir=bench_dir,
                            controls=controls)
    correct = verdict["ok"] and not failed

    metrics = {}
    if dev.platform != "tpu":
        pass            # nothing is measured off the chip: metrics withheld
    elif not trace:
        names = [m["name"] for m in spec.metrics_for(workload, "end_to_end",
                                                    root)]
        values = end_to_end(rec, setup_s)
        for m in spec.metrics_for(workload, "end_to_end", root):
            if m["name"] in values:
                metrics[m["name"]] = {"value": values[m["name"]],
                                      "unit": m["unit"]}
        missing = set(names) - set(metrics)
        if missing:
            raise RuntimeError(f"end-to-end metrics not measured: {missing}")
    else:
        for m in spec.metrics_for(workload, "per_layer", root):
            reader = spec.load_module("layer_metrics", m["name"], bench_dir)
            value = reader.read(rec) if reader is not None else None
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": mem}
    result = {"correct": bool(correct), "attempted": len(attempted),
              "failed": len(failed), "metrics": metrics, "device": device}
    if red is not None:
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]
        result["breakdown"] = tr.breakdown(red)
    log(f"compared with the reference: {verdict['requests']} requests, "
        f"{verdict['tokens']} served tokens; gaps below the reference's "
        f"best: {verdict['gaps']}")
    checks = {name: {"value": v["value"], "limit": v["limit"]}
              for name, v in verdict["numbers"].items()}
    checks["failed_requests"] = {"value": len(failed), "limit": 0}
    for name, v in checks.items():
        log(f"check {name}: {v['value']!r} (limit {v['limit']!r})")
    if controls or mutate is not None:
        result["gaps"] = verdict["gaps"]
        result["controls"] = verdict["controls"]
    result["checks"] = checks
    return result, rec


def end_to_end(rec: Dict, setup_s: float) -> Dict[str, float]:
    """The end-to-end metrics of a window, from the client's side: every
    output token delivered by a step that returned inside the window, over
    the window's seconds."""
    t0, t_end = rec["t0"], rec["t_end"]
    n = sum(n for s in rec["served"].values()
            for t, n in s.deliveries if t0 <= t <= t_end)
    return {"setup_s": setup_s, "output_tok_s": n / (t_end - t0)}
