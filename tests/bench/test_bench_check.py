"""The correctness check fails what it must: its control and planted faults.

At the cut-down granite configuration on the CPU, a whole run (everything
but the look for a chip) is driven with the timed path broken underneath
(``chipbench.faults``), and ``correct`` must come out false; the control —
the reference computed with FP8 activations in place of the program — must
read above the limit that sound runs stay under, and the check's verdict on
it must be false.  The number compared is the mean gap of the served tokens
below the reference's best logit.  The test cell's limit was set the way
the chip cell's is, from readings on the CPU over seeds 1-12 (1 s windows,
every finished request compared): above the largest mean gap sound runs
gave (0.00023) and below the smallest the control gave (0.00313).
"""
import pytest

import smoke_cell
from chipbench import faults
from chipbench.cell import run_cell

LIMITS = {"smoke-closed": 0.001}
SEEDS = {"smoke-closed": 1}


@pytest.fixture(scope="module")
def copies(tmp_path_factory):
    made = {}

    def get(mix):
        if mix not in made:
            root, bench, names = smoke_cell.make_copy(
                tmp_path_factory.mktemp(mix), [("granite-smoke", mix)],
                limit=LIMITS[mix])
            made[mix] = (mix, root, bench, names[0])
        return made[mix]
    return get


def _run(cell, mutate=None, controls=()):
    mix, root, bench, name = cell
    return run_cell(name, SEEDS[mix], 1.0, False, root=root,
                    bench_dir=bench, mutate=mutate, controls=controls)


@pytest.mark.parametrize("mix", ["smoke-closed"])
def test_sound_run_passes_and_control_fails(copies, mix):
    r = _run(copies(mix), controls=("fp8",))
    gap = r["checks"]["mean_logit_gap"]
    assert r["correct"] is True and gap["value"] <= gap["limit"]
    control = r["controls"]["ref_fp8"]
    assert control["mean"] > gap["limit"] and control["ok"] is False


@pytest.mark.parametrize("mix,fault", [
    ("smoke-closed", "state_unchanged"), ("smoke-closed", "half_batch"),
    ("smoke-closed", "token_altered")],
    ids=["closed-state_unchanged", "closed-half_batch",
         "closed-token_altered"])
def test_broken_timed_path_is_not_correct(copies, mix, fault):
    r = _run(copies(mix), mutate=faults.FAULTS[fault])
    gap = r["checks"]["mean_logit_gap"]
    assert r["correct"] is False, gap
    assert gap["value"] > gap["limit"]
