"""Kernels: the quantized weight products' share of their roofline.

Sum over the window's decode sub-steps of the least time the chip could
take for the quantized linears alone — every layer's packed codes and
scales read once (``chipbench.costs.layer_linears``), 2 FLOPs per weight
per row — over the device seconds under the program's ``qmatmul`` scope
(``chipbench.scopes``), in %."""
from chipbench import costs, records


def linears_step(cfg_file, rows):
    """FLOPs and bytes of the quantized linears of one decode sub-step of
    ``rows`` rows."""
    m, w = cfg_file["model"], cfg_file["weights"]
    nbytes = weights = 0.0
    for kind, mats in costs.layer_linears(m).items():
        fmt = w[kind]
        for k, n in mats:
            weights += k * n
            nbytes += k * n * costs._BITS[fmt["format"]] / 8
            nbytes += k // fmt["group"] * n * costs._SCALE_BYTES[fmt["scale"]]
    return {"flops": 2.0 * rows * weights * m["n_layers"],
            "bytes": nbytes * m["n_layers"]}


def read(rec):
    red = rec.get("scopes")
    secs = (red or {}).get("scopes", {}).get("qmatmul")
    subs = records.decode_substeps(rec)
    if not secs or not subs:
        return None
    pk = costs.peak(rec["device_kind"])
    need = sum(costs.roofline_seconds(linears_step(rec["cfg_file"], len(lv)),
                                      pk) for lv in subs)
    return 100.0 * need / secs
