"""Kernels: the quantized weight products' share of their roofline.

Sum over the window's decode sub-steps of the least time the chip could
take for the quantized linears alone — every layer's codes and scales read
once, at the bytes their weight format costs, 2 FLOPs per weight per row
(``chipbench.costs.linear_cost``) — over the device seconds under the
program's ``qmatmul`` scope (``chipbench.scopes``), in %."""
from chipbench import costs, records


def linears_step(cfg_file, rows, bench_dir=None):
    """FLOPs and bytes of the quantized linears of one decode sub-step of
    ``rows`` rows."""
    layers = cfg_file["model"]["n_layers"]
    c = costs.linear_cost(cfg_file, bench_dir)
    return {"flops": 2.0 * rows * c["weights"] * layers,
            "bytes": float(c["bytes"] * layers)}


def read(rec):
    red = rec.get("scopes")
    secs = (red or {}).get("scopes", {}).get("qmatmul")
    subs = records.decode_substeps(rec)
    if not secs or not subs:
        return None
    pk = costs.peak(rec["device_kind"])
    need = sum(costs.roofline_seconds(
        linears_step(rec["cfg_file"], len(lv), rec.get("bench_dir")), pk)
        for lv in subs)
    return 100.0 * need / secs
