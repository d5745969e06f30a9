"""The program's scopes and spans, read from a trace: the reduction and its
readers.

``benchmarks/chip/testdata/smoke_scoped.xplane.pb`` was recorded once on a
TPU v5e by a ``--trace 1`` run of the cut-down granite cell
(``tools/scope_split.py``, closed loop, a 0.15 s window) with the named
scopes and ``serve.*`` spans in the program.  ``smoke_closed.xplane.pb``
predates them.
"""
import os

import pytest

import smoke_cell
from chipbench import costs, scopes, spec, trace

TESTDATA = os.path.join(smoke_cell.BENCH, "testdata")
SCOPED = os.path.join(TESTDATA, "smoke_scoped.xplane.pb")
UNSCOPED = os.path.join(TESTDATA, "smoke_closed.xplane.pb")
READERS = ("qmatmul_ms.batch", "qmatmul_roofline.batch",
           "decode_attention_ms.batch", "kv_write_ms.batch",
           "host_step_ms.batch")


def test_wire_reader_gives_profile_data_times():
    """Every event of every line, at the times ProfileData gives."""
    from jax.profiler import ProfileData
    mine = {name: lines for name, lines, *_ in scopes.read_space(UNSCOPED)}
    n = 0
    for plane in ProfileData.from_file(UNSCOPED).planes:
        for line in plane.lines:
            want = [(e.start_ns, e.end_ns) for e in line.events]
            got = [(s, e) for s, e, _, _ in mine[plane.name].get(line.name,
                                                                 [])]
            assert got == want, (plane.name, line.name)
            n += len(want)
    assert n > 10000


def test_scope_of_takes_the_innermost_known_scope():
    path = ("jit(decode_burst)/while/body/closed_call/attn/attn_core/"
            "kv_write/scatter")
    assert scopes.scope_of(path) == "kv_write"
    assert scopes.scope_of("jit(f)/attn/qmatmul/jit(_packed_matmul_impl)/"
                           "packed_gemv/while/body/dot_general") == "qmatmul"
    assert scopes.scope_of("jit(decode_burst)/while/body/concatenate") == \
        scopes.UNATTRIBUTED
    assert scopes.scope_of(None) == scopes.UNATTRIBUTED


def _decode_op_seconds(path):
    red = trace.reduce(path)
    return sum(v for k, v in red["ops"].items() if k.startswith(scopes.DECODE))


def test_recorded_scoped_trace():
    """The scopes partition the decode programs' op seconds exactly; one
    ``serve.decode`` span per decode program run and one ``serve.prefill``
    per prefill chunk; the split is pinned as recorded."""
    red = scopes.reduce(SCOPED)
    assert red["ops_s"] == pytest.approx(_decode_op_seconds(SCOPED),
                                         abs=1e-9)
    assert sum(red["scopes"].values()) == pytest.approx(red["ops_s"],
                                                        abs=1e-12)
    programs = trace.reduce(SCOPED)["programs"]
    assert red["spans"]["serve.decode"]["count"] == \
        programs["jit_decode_slots"]["count"] == 25
    assert red["spans"]["serve.prefill"]["count"] == \
        programs["jit_prefill_chunk"]["count"] == 24
    assert red["spans"]["serve.step"]["count"] == 25
    assert red["scopes"] == pytest.approx({
        "qmatmul": 0.000197182, "attn_core": 5.1107e-05,
        "kv_write": 5.671e-05, "lm_head": 1.019e-05, "sample": 3.3564e-05,
        "attn": 3.1274e-05, "ffn": 1.571e-06, "unattributed": 0.0002592},
        rel=1e-9)
    assert red["host_step"]["seconds"] == pytest.approx(0.120540954,
                                                        rel=1e-9)
    rec = record_with_steps()
    rec["scopes"] = red
    for name in READERS:
        assert spec.load_module("layer_metrics", name).read(rec) > 0


def test_trace_without_scopes_is_all_unattributed():
    red = scopes.reduce(UNSCOPED)
    assert set(red["scopes"]) == {scopes.UNATTRIBUTED}
    assert red["ops_s"] == pytest.approx(_decode_op_seconds(UNSCOPED),
                                         abs=1e-9)
    assert red["spans"] == {} and red["host_step"]["count"] == 0
    rec = record_with_steps()
    rec["scopes"] = red
    for name in READERS:
        assert spec.load_module("layer_metrics", name).read(rec) is None


# --- a hand-built trace ------------------------------------------------------
def _varint(n):
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _msg(*fields):
    """A protobuf message from (field number, int | str | bytes) pairs."""
    out = b""
    for num, val in fields:
        if isinstance(val, int):
            out += _varint(num << 3) + _varint(val)
        else:
            val = val.encode() if isinstance(val, str) else val
            out += _varint(num << 3 | 2) + _varint(len(val)) + val
    return out


def _plane(name, events_meta, stat_names, lines):
    """XPlane: ``events_meta`` {id: (name, [(stat id, str | ref id)])},
    ``lines`` {name: (timestamp_ns, [(metadata id, start_ps, dur_ps,
    [(stat id, int)])])}."""
    fields = [(2, name)]
    for ln_name, (t0, events) in lines.items():
        evs = [(4, _msg((1, mid), (2, off), (3, dur),
                        *[(4, _msg((1, sid), (4, v))) for sid, v in st]))
               for mid, off, dur, st in events]
        fields.append((3, _msg((2, ln_name), (3, t0), *evs)))
    for mid, (ev_name, stats) in events_meta.items():
        st = [(5, _msg((1, sid), (5, v) if isinstance(v, str) else (7, v)))
              for sid, v in stats]
        fields.append((4, _msg((1, mid), (2, _msg((1, mid), (2, ev_name),
                                                  *st)))))
    for sid, sname in stat_names.items():
        fields.append((5, _msg((1, sid), (2, _msg((1, sid), (2, sname))))))
    return _msg(*fields)


def _hand_trace(path):
    """A window [1000, 9000] ns.  Host: two serve.step spans holding
    serve.sync spans.  Device: a decode burst whose while op holds a
    qmatmul op (tf_op by reference), a kv_write op and an op with no
    scope; a prefill op with the same HLO text as the qmatmul op; a
    decode op after the window."""
    us = 1000 * 1000                      # one microsecond in ps
    host = _plane("/host:CPU", {
        1: ("bench.window", []), 2: ("serve.step", []),
        3: ("serve.sync", []), 4: ("serve.decode", [])},
        {1: "step_num"}, {"main/1": (0, [
            (1, 1 * us, 8 * us, []),
            (2, 2 * us, 3 * us, [(1, 0)]), (4, 2 * us, 3 * us, []),
            (3, 4 * us, 1 * us, []),
            (2, 6 * us, 2 * us, [(1, 1)]), (3, 6 * us, 500 * 1000, []),
            (2, 20 * us, 1 * us, [(1, 2)])])})     # after the window
    path_q = "jit(decode_burst)/while/body/attn/qmatmul/dot_general"
    dev = _plane("/device:TPU:0", {
        10: ("jit_decode_burst(1)", []), 11: ("jit_prefill_chunk(2)", []),
        20: ("%fusion.1 = bf16[4]{0} fusion(%p)", [(1, 99)]),
        21: ("%while.2 = (s32[]) while(%t)",
             [(1, "jit(decode_burst)/while")]),
        22: ("%scatter.3 = bf16[4]{0} scatter(%a)",
             [(1, "jit(decode_burst)/while/body/attn/attn_core/kv_write/"
                  "scatter")]),
        23: ("%fusion.1 = bf16[4]{0} fusion(%p)",
             [(1, "jit(prefill_chunk)/attn/qmatmul/dot_general")]),
        24: ("%copy.4 = bf16[4]{0} copy(%c)",
             [(1, "jit(decode_burst)/copy")])},
        {1: "tf_op", 99: path_q}, {
            "XLA Modules": (0, [(10, 2 * us, 5 * us, []),
                                (11, 7 * us, 1 * us, []),
                                (10, 10 * us, 1 * us, [])]),
            "XLA Ops": (0, [(21, 2 * us, 4 * us, []),
                            (20, 2500 * 1000, 2 * us, []),
                            (22, 5 * us, 250 * 1000, []),
                            (24, 6500 * 1000, 250 * 1000, []),
                            (23, 7 * us, 1 * us, []),
                            (20, 10 * us, 1 * us, [])])})
    with open(path, "wb") as f:
        f.write(_msg((1, host), (1, dev)))


def test_hand_built_trace(tmp_path):
    path = str(tmp_path / "hand.xplane.pb")
    _hand_trace(path)
    red = scopes.reduce(path)
    assert red["scopes"] == pytest.approx({
        "qmatmul": 2e-6,                 # not the prefill op of that text
        "kv_write": 0.25e-6,
        "unattributed": (4 - 2 - 0.25) * 1e-6 + 0.25e-6})
    assert red["ops_s"] == pytest.approx(_decode_op_seconds(path), abs=1e-9)
    assert red["spans"] == {
        "serve.step": {"count": 2, "seconds": pytest.approx(5e-6)},
        "serve.decode": {"count": 1, "seconds": pytest.approx(3e-6)},
        "serve.sync": {"count": 2, "seconds": pytest.approx(1.5e-6)}}
    assert red["host_step"] == {"count": 2,
                                "seconds": pytest.approx(3.5e-6)}
    assert "qmatmul" in scopes.table(red)


# --- the readers on hand-made records ---------------------------------------
def record_with_steps():
    rec = {"t0": 0.0, "t_end": 5.0, "n_slots": 4, "seconds": 5.0,
           "cfg_file": spec.load_config("granite-8b"),
           "device_kind": "TPU v5 lite", "prefill_partial": [],
           "served": {}}
    rec["steps"] = [
        # request A (prompt 10) gets its first token and one decode token;
        # request B (prompt 20) a burst of 4 decode tokens
        {"t": 1.0, "token_steps": 4, "rows": [(10, 0, 2), (20, 5, 4)]},
        {"t": 6.0, "token_steps": 1, "rows": [(10, 2, 1)]},   # after close
    ]
    return rec


SCOPE_SECONDS = {"qmatmul": 0.8, "attn_core": 0.04, "kv_write": 0.012,
                 "lm_head": 0.02, "unattributed": 0.1}


def _linears_roofline(rec):
    """The quantized linears' least seconds over the record's decode
    sub-steps ([11, 25], [26], [27], [28]), by hand: 36 layers of
    4096x4096 (q, o), 4096x1024 (k, v) and 4096x14336 (gate, up, down)
    int4 codes and f32 scales per 128 inputs."""
    pk = costs.peak("TPU v5 lite")
    weights = 36 * (2 * 4096 * 4096 + 2 * 4096 * 1024 + 3 * 4096 * 14336)
    nbytes = weights / 2 + weights / 128 * 4
    assert nbytes == 4171235328
    return sum(max(2 * rows * weights / pk["bf16_flops_per_s"],
                   nbytes / pk["hbm_bytes_per_s"]) for rows in (2, 1, 1, 1))


@pytest.mark.parametrize("name,want", [
    ("qmatmul_ms.batch", 1e3 * 0.8 / 4),
    ("qmatmul_roofline.batch", lambda rec: 100 * _linears_roofline(rec) / 0.8),
    ("decode_attention_ms.batch", 1e3 * 0.04 / 4),
    ("kv_write_ms.batch", 1e3 * 0.012 / 4),
    ("host_step_ms.batch", 1e3 * 0.009 / 3),
])
def test_scope_readers(name, want):
    """Each reader over the window's decode token-steps (4: the step after
    the window's close is not counted) or rounds, and None without the
    program's scopes."""
    reader = spec.load_module("layer_metrics", name)
    rec = record_with_steps()
    assert reader.read(rec) is None
    rec["scopes"] = {"scopes": {"unattributed": 1.0}, "ops_s": 1.0,
                     "spans": {}, "host_step": {"count": 0, "seconds": 0.0}}
    assert reader.read(rec) is None
    rec["scopes"] = {"scopes": dict(SCOPE_SECONDS),
                     "ops_s": sum(SCOPE_SECONDS.values()),
                     "spans": {"serve.step": {"count": 3, "seconds": 0.5}},
                     "host_step": {"count": 3, "seconds": 0.009}}
    want = want(rec) if callable(want) else want
    assert reader.read(rec) == pytest.approx(want, rel=1e-12)
