"""The benchmark's byte and FLOP counts against hand arithmetic."""
import pytest

import smoke_cell  # noqa: F401  (puts the benchmark on the path)
from chipbench import costs, spec, weights


@pytest.fixture(scope="module")
def granite():
    return spec.load_config("granite-8b")


def test_granite_weight_bytes_by_hand(granite):
    # per layer: q, o 4096 x 4096; k, v 4096 x 1024; gate, up 4096 x 14336;
    # down 14336 x 4096 -> 218,103,808 weights at 4 bits, one f32 scale
    # per 128 of them
    per_layer = 2 * 4096 * 4096 + 2 * 4096 * 1024 + 3 * 4096 * 14336
    assert per_layer == 218_103_808
    layers = 36 * (per_layer // 2 + per_layer // 128 * 4)
    lm_head = 4096 * 49152 * 2
    norms = (2 * 36 + 1) * 4096 * 4
    assert costs.weight_bytes(granite) == layers + lm_head + norms \
        == 4_575_084_544


@pytest.mark.parametrize("rows,live", [(32, 512), (16, 1280), (1, 17)])
def test_granite_decode_step_by_hand(granite, rows, live):
    kv = 36 * 2 * 8 * 128 * 2                  # bf16 K and V, all layers
    assert costs.kv_bytes_per_token(granite) == kv == 147_456
    got = costs.decode_step(granite, [live] * rows)
    want_bytes = 4_575_084_544 + rows * (4096 * 2 + live * kv + kv)
    per_row = 2 * (36 * 218_103_808 + 4096 * 49152) \
        + 4 * live * 32 * 128 * 36
    assert got["bytes"] == want_bytes
    assert got["flops"] == rows * per_row
    # 32 rows at 512 positions: the weights and about 2.4 GB of K/V reads
    if rows == 32:
        assert got["bytes"] == pytest.approx(4.575e9 + 2.4207e9, rel=1e-3)


def test_prefill_token_flops_by_hand(granite):
    body = 2 * 36 * 218_103_808
    assert costs.prefill_token_flops(granite, 0, False) == \
        body + 4 * 1 * 32 * 128 * 36
    assert costs.prefill_token_flops(granite, 99, True) == \
        body + 2 * 4096 * 49152 + 4 * 100 * 32 * 128 * 36


def test_roofline_takes_the_larger_bound():
    pk = costs.peak("TPU v5 lite")
    assert costs.roofline_seconds({"flops": 197e12, "bytes": 1.0}, pk) == 1.0
    assert costs.roofline_seconds({"flops": 1.0, "bytes": 819e9}, pk) == 1.0
    with pytest.raises(KeyError):
        costs.peak("no such chip")


@pytest.mark.parametrize("fmt", [
    {"format": "int3", "group": 32, "scale": "f32"},
    {"format": "int4", "group": 128, "scale": "bf16"}], ids=["int3", "int4-bf16"])
def test_formats_without_a_rule_are_refused(granite, fmt):
    """A configuration whose weights have no drawing or counting rule fails
    loudly instead of being measured against the wrong yardstick."""
    with pytest.raises(ValueError):
        weights.check_format(fmt)
    other = dict(granite, weights=dict(granite["weights"], ffn=fmt))
    with pytest.raises(KeyError):
        costs.weight_bytes(other)
