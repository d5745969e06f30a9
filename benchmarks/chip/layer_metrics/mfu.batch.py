"""Whole step: model FLOPs of the window over the window at the bf16 peak.

Every prompt token prefilled and every token decoded in the traced window,
at the FLOPs the model needs for it (``chipbench.costs``), over the traced
window's length times the chip's bf16 peak."""
from chipbench import costs, records


def read(rec):
    red = rec.get("trace")
    if not red or not red["window_s"]:
        return None
    cfg, bench_dir = rec["cfg_file"], rec.get("bench_dir")
    flops = 0.0
    for lives in records.decode_substeps(rec):
        flops += costs.decode_step(cfg, lives, bench_dir)["flops"]
    t0, t1 = rec["t0"], rec["t_end"]
    for s in rec["served"].values():
        done = s.deliveries and t0 <= s.deliveries[0][0] <= t1 \
            and s.sent >= t0
        if done:
            n = s.req.prompt_len
            flops += sum(costs.prefill_token_flops(cfg, p, p == n - 1,
                                                 bench_dir)
                         for p in range(n))
    for n, pos in rec["prefill_partial"]:
        flops += sum(costs.prefill_token_flops(cfg, p, False, bench_dir)
                     for p in range(pos))
    if not flops:
        return None
    pk = costs.peak(rec["device_kind"])
    return 100.0 * flops / (red["window_s"] * pk["bf16_flops_per_s"])
