"""The ``elementwise_ms.batch`` reader: the blocks' own op seconds (scopes
``attn`` and ``ffn``) per decode token-step."""
import pytest

import smoke_cell  # noqa: F401  (puts the benchmark on the path)
from chipbench import scopes, spec
from test_bench_scopes import SCOPED, UNSCOPED, record_with_steps

READER = spec.load_module("layer_metrics", "elementwise_ms.batch")


def _scoped(seconds):
    return {"scopes": dict(seconds), "ops_s": sum(seconds.values()),
            "spans": {}, "host_step": {"count": 0, "seconds": 0.0}}


@pytest.mark.parametrize("seconds,want", [
    # 4 decode token-steps in the record's window
    ({"attn": 0.02, "ffn": 0.006, "qmatmul": 0.8}, 1e3 * 0.026 / 4),
    ({"attn": 0.02, "qmatmul": 0.8}, 1e3 * 0.02 / 4),
    ({"ffn": 0.006, "attn_core": 0.04}, 1e3 * 0.006 / 4),
    ({"qmatmul": 0.8, "unattributed": 1.0}, None),
])
def test_reads_attn_and_ffn_self_seconds(seconds, want):
    """Only the ``attn`` and ``ffn`` scopes count (not ``qmatmul``,
    ``attn_core`` or what no scope claims); nothing where neither is in
    the trace, or where there is no reduction at all."""
    rec = record_with_steps()
    assert READER.read(rec) is None
    rec["scopes"] = _scoped(seconds)
    got = READER.read(rec)
    assert got == (pytest.approx(want) if want is not None else None)


@pytest.mark.parametrize("path,found", [(SCOPED, True), (UNSCOPED, False)])
def test_recorded_traces(path, found):
    """On the recorded cut-down granite traces: the scoped trace's ``attn``
    and ``ffn`` seconds over the token-steps, and nothing from the trace
    recorded before the program had scopes."""
    rec = record_with_steps()
    rec["scopes"] = scopes.reduce(path)
    got = READER.read(rec)
    if not found:
        assert got is None
        return
    s = rec["scopes"]["scopes"]
    assert got == pytest.approx(1e3 * (s["attn"] + s["ffn"]) / 4)
