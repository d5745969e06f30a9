"""Kernels (the decode step taken whole): share of its roofline.

Sum over the window's decode sub-steps of the least time the chip could
take for the work the step needs (``chipbench.costs.decode_step``: weights
once per sub-step, each row's K/V reads at its live length) over the
device seconds of the decode programs."""
from chipbench import costs, records, trace


def read(rec):
    red = rec.get("trace")
    if not red:
        return None
    secs, runs = trace.program_seconds(red, ("jit_decode_slots",
                                             "jit_decode_burst"))
    subs = records.decode_substeps(rec)
    if not runs or not secs or not subs:
        return None
    pk = costs.peak(rec["device_kind"])
    need = sum(costs.roofline_seconds(
        costs.decode_step(rec["cfg_file"], lv, rec.get("bench_dir")), pk)
        for lv in subs)
    return 100.0 * need / secs
