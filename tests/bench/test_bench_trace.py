"""The trace reduction against sums made by hand on a recorded trace.

``benchmarks/chip/testdata/smoke_closed.xplane.pb`` was recorded once on a
TPU v5e by a ``--trace 1`` run of the cut-down granite cell (closed loop,
a 0.15 s window).  The busy time, the idle share and each program's device
time are summed here again in the plainest way and compared with
``chipbench.trace.reduce``; the totals are pinned as well.
"""
import os

import pytest

import smoke_cell
from chipbench import trace

TRACE = os.path.join(smoke_cell.BENCH, "testdata", "smoke_closed.xplane.pb")


@pytest.fixture(scope="module")
def recorded():
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(TRACE)
    window = None
    for plane in pd.planes:
        for line in plane.lines:
            for e in line.events:
                if e.name == "bench.window":
                    window = (e.start_ns, e.end_ns)
    device = [p for p in pd.planes if p.name == "/device:TPU:0"][0]
    lines = {ln.name: list(ln.events) for ln in device.lines}
    return window, lines, trace.reduce(TRACE)


def test_busy_time_is_the_union_of_op_intervals(recorded):
    (w0, w1), lines, red = recorded
    # mark every nanosecond an operation covers, on a sorted sweep
    ops = sorted((max(e.start_ns, w0), min(e.end_ns, w1))
                 for e in lines["XLA Ops"] if e.end_ns > w0 and e.start_ns < w1)
    busy, reach = 0.0, w0
    for s, e in ops:
        if e > reach:
            busy += e - max(s, reach)
            reach = e
    assert red["window_s"] == pytest.approx((w1 - w0) / 1e9, rel=1e-12)
    assert red["busy_s"] == pytest.approx(busy / 1e9, rel=1e-9)
    assert 0.0 < red["busy_s"] < red["window_s"]
    idle = 1.0 - red["busy_s"] / red["window_s"]
    assert idle == pytest.approx(1.0 - busy / (w1 - w0), rel=1e-9)


def test_program_times_are_module_sums(recorded):
    (w0, w1), lines, red = recorded
    sums = {}
    for e in lines["XLA Modules"]:
        if e.start_ns >= w0 and e.end_ns <= w1:
            name = e.name[:e.name.index("(")]
            t, c = sums.get(name, (0.0, 0))
            sums[name] = (t + e.duration_ns / 1e9, c + 1)
    assert set(sums) == set(red["programs"])
    for name, (t, c) in sums.items():
        assert red["programs"][name]["seconds"] == pytest.approx(t, rel=1e-9)
        assert red["programs"][name]["count"] == c
    for prog in ("jit_prefill_chunk", "jit_decode_slots", "jit_decode_burst"):
        assert sums[prog][1] > 0
    decode, runs = trace.program_seconds(red, ("jit_decode_slots",
                                               "jit_decode_burst"))
    assert runs == sums["jit_decode_slots"][1] + sums["jit_decode_burst"][1]
    assert decode == pytest.approx(sums["jit_decode_slots"][0]
                                   + sums["jit_decode_burst"][0], rel=1e-9)


def test_op_self_times_and_idle_add_up(recorded):
    (w0, w1), _, red = recorded
    # self times partition the time operations ran; idle time is the rest
    idle = sum(v["seconds"] for v in red["idle"].values())
    assert idle == pytest.approx(red["window_s"] - red["busy_s"], rel=1e-6)
    assert all("/" in name for name in red["ops"])
    b = trace.breakdown(red)
    assert len(b["device_ops"]) == 10 and len(b["idle_gaps"]) <= 10
    assert all(name.startswith(("step", "submit", "none"))
               for name, _ in b["idle_gaps"])


def test_recorded_totals_are_pinned(recorded):
    _, _, red = recorded
    assert red["window_s"] == pytest.approx(0.157418091, rel=1e-9)
    assert red["busy_s"] == pytest.approx(0.001372832, rel=1e-9)
    counts = {k: v["count"] for k, v in red["programs"].items()}
    assert counts["jit_decode_burst"] == 7
    assert counts["jit_decode_slots"] == 16
    assert counts["jit_prefill_chunk"] == 18
    assert red["programs"]["jit_decode_burst"]["seconds"] == \
        pytest.approx(0.000583191, rel=1e-9)


def test_set_operations():
    assert trace.union([(0, 2), (1, 3), (5, 6), (6, 7)]) == [(0, 3), (5, 7)]
    assert trace.clip([(0, 2), (3, 9)], 1, 4) == [(1, 2), (3, 4)]
    assert trace.self_times([(0, 10, "while"), (1, 4, "a"), (5, 6, "b"),
                             (2, 3, "c")]) == {"while": 6, "a": 2, "b": 1,
                                               "c": 1}
    assert trace.op_name("%fusion.3 = bf16[2]{0} fusion(%p)") == "fusion.3"
