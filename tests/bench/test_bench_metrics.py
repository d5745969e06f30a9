"""The end-to-end and per-layer arithmetic on a hand-made run record."""
import types

import pytest

import smoke_cell  # noqa: F401  (puts the benchmark on the path)
from chipbench import cell, costs, spec


def served(prompt_len, sent, deliveries):
    s = cell.Served(types.SimpleNamespace(prompt_len=prompt_len), sent)
    s.deliveries = list(deliveries)
    return s


def test_closed_loop_rate_counts_the_window():
    rec = {"t0": 10.0, "t_end": 14.0, "seconds": 4.0,
           "served": {1: served(8, 8.0, [(9.0, 1), (11.0, 8), (14.0, 8)]),
                      2: served(8, 9.0, [(12.0, 1), (15.0, 8)])}}
    out = cell.end_to_end(rec, setup_s=3.5)
    assert out == {"setup_s": 3.5, "output_tok_s": (8 + 8 + 1) / 4.0}


@pytest.mark.parametrize("t_end,want", [(12.0, 9 / 2.0), (16.0, 25 / 6.0)])
def test_rate_is_over_all_the_window(t_end, want):
    """Every token delivered in the window counts, over all its seconds,
    however the steps fall in it."""
    rec = {"t0": 10.0, "t_end": t_end, "seconds": t_end - 10.0,
           "served": {1: served(8, 8.0, [(9.0, 1), (11.0, 8), (14.0, 8)]),
                      2: served(8, 9.0, [(12.0, 1), (15.0, 8)])}}
    assert cell.end_to_end(rec, 0.0)["output_tok_s"] == pytest.approx(want)


def record_with_steps():
    rec = {"t0": 0.0, "t_end": 5.0, "n_slots": 4, "seconds": 5.0,
           "cfg_file": spec.load_config("granite-8b"),
           "device_kind": "TPU v5 lite", "prefill_partial": [(40, 16)],
           "served": {}}
    rec["steps"] = [
        # request A (prompt 10) gets its first token and one decode token;
        # request B (prompt 20) a burst of 4 decode tokens
        {"t": 1.0, "token_steps": 4, "rows": [(10, 0, 2), (20, 5, 4)]},
        {"t": 6.0, "token_steps": 1, "rows": [(10, 2, 1)]},   # after close
    ]
    return rec


def test_decode_substeps_and_occupancy():
    rec = record_with_steps()
    from chipbench import records
    assert records.decode_substeps(rec) == [[11, 25], [26], [27], [28]]
    occ = spec.load_module("layer_metrics", "slot_occupancy.batch")
    assert occ.read(rec) == pytest.approx(100.0 * 5 / (4 * 4))


def test_trace_readers_need_a_trace_and_divide_by_device_time():
    rec = record_with_steps()
    names = ("decode_step_ms.batch", "prefill_chunk_ms.batch",
             "decode_step_roofline", "mfu.batch", "idle_share.batch")
    readers = {n: spec.load_module("layer_metrics", n) for n in names}
    assert all(r.read(rec) is None for r in readers.values())
    rec["trace"] = {"window_s": 5.0, "busy_s": 4.0, "programs": {
        "jit_decode_burst": {"seconds": 0.8, "count": 1},
        "jit_prefill_chunk": {"seconds": 0.3, "count": 2}}}
    assert readers["decode_step_ms.batch"].read(rec) == pytest.approx(200.0)
    assert readers["prefill_chunk_ms.batch"].read(rec) == pytest.approx(150.0)
    assert readers["idle_share.batch"].read(rec) == pytest.approx(20.0)
    cfg, pk = rec["cfg_file"], costs.peak("TPU v5 lite")
    need = sum(costs.roofline_seconds(costs.decode_step(cfg, lv), pk)
               for lv in ([11, 25], [26], [27], [28]))
    assert readers["decode_step_roofline"].read(rec) == \
        pytest.approx(100.0 * need / 0.8)
    flops = sum(costs.decode_step(cfg, lv)["flops"]
                for lv in ([11, 25], [26], [27], [28]))
    flops += sum(costs.prefill_token_flops(cfg, p, False) for p in range(16))
    assert readers["mfu.batch"].read(rec) == \
        pytest.approx(100.0 * flops / (5.0 * pk["bf16_flops_per_s"]))
