"""The harness is driven by data: a cell added as files runs, unedited code.

A traffic file and a cell entry placed in a temporary copy of the benchmark
are found by name and run on the CPU at cut-down configurations (granite's
gated SiLU FFN, starcoder2's plain GELU FFN), through the generator, the
serving path and the comparison with the plain reference.  Off a TPU the
metrics are withheld, and the measuring entry point exits non-zero with no
result line.
"""
import json
import os
import shutil
import subprocess
import sys

import pytest

import smoke_cell
from chipbench import scopes, trace
from chipbench.cell import run_cell

SEED = 2**31 + 12345
SCOPED = os.path.join(smoke_cell.BENCH, "testdata", "smoke_scoped.xplane.pb")


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    return smoke_cell.make_copy(tmp_path_factory.mktemp("bench"),
                                [("granite-smoke", "smoke-closed"),
                                 ("starcoder2-smoke", "smoke-closed")])


@pytest.mark.parametrize("which", [0, 1])
def test_cell_added_as_files_runs_and_is_correct(copy, which):
    root, bench, names = copy
    assert not os.path.exists(os.path.join(smoke_cell.BENCH, "traffic",
                                           "smoke-closed.json"))
    r, _ = run_cell(names[which], SEED, 1.0, False, root=root,
                    bench_dir=bench)
    assert r["correct"] is True, r
    assert r["failed"] == 0 and r["attempted"] >= 1
    assert r["metrics"] == {}                 # withheld off the chip
    assert r["device"]["platform"] == "cpu"
    gap = r["checks"]["mean_logit_gap"]
    assert 0.0 <= gap["value"] <= gap["limit"]
    assert list(r)[-1] == "checks"


def test_traced_record_carries_the_scopes(copy, monkeypatch, capsys):
    """A traced run hands its readers the reduction by the program's scopes
    of the trace file it reduces for device time, and logs it.  A CPU trace
    has no device plane, so a trace recorded on a TPU v5e stands in for the
    run's own."""
    root, bench, names = copy
    monkeypatch.setattr(trace, "find_xplane",
                        lambda trace_dir: shutil.copy(SCOPED, trace_dir))
    r, rec = run_cell(names[0], SEED, 1.0, True, root=root, bench_dir=bench)
    assert r["correct"] is True
    assert rec["scopes"] == scopes.reduce(SCOPED)
    assert rec["scopes"]["scopes"]["qmatmul"] > 0
    assert r["device"]["busy_s"] == trace.reduce(SCOPED)["busy_s"]
    assert "scopes of the decode programs" in capsys.readouterr().err


def _run_py(cwd, *extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, os.path.join(cwd, "benchmarks", "chip", "run.py"),
         "--workload", "granite-8b.batch-decode", "--seed", "3",
         "--seconds", "1", "--trace", "0", *extra],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120)


def test_measurement_path_refuses_the_cpu():
    p = _run_py(smoke_cell.ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr


def test_benchmark_alone_exits_without_a_result(tmp_path):
    shutil.copy(os.path.join(smoke_cell.ROOT, "BENCHMARK.json"), tmp_path)
    with open(os.path.join(smoke_cell.ROOT, "BENCHMARK.json")) as f:
        paths = json.load(f)["paths"]
    for p in paths:
        shutil.copytree(os.path.join(smoke_cell.ROOT, p),
                        os.path.join(tmp_path, p),
                        ignore=shutil.ignore_patterns("__pycache__"))
    p = _run_py(str(tmp_path))
    assert p.returncode != 0
    assert p.stdout.strip() == ""
