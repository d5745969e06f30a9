"""The correctness check fails what it must: its control and planted faults.

At cut-down configurations on the CPU, a whole run (everything
but the look for a chip) is driven with the timed path broken underneath
(``chipbench.faults``), and ``correct`` must come out false; the control —
the reference computed with FP8 activations in place of the program — must
read above the limit that sound runs stay under, and the check's verdict on
it must be false.  The number compared is the mean gap of the served tokens
below the reference's best logit.  The test cell's limit was set the way
the chip cell's is, from readings on the CPU over seeds 1-12 (1 s windows,
every finished request compared): above the largest mean gap sound runs
gave (granite-smoke 0.00023, starcoder2-smoke's plain GELU FFN 0.000126)
and below the smallest the control gave (0.00313, 0.00178).  A plain FFN
reference with SiLU in place of GELU reads 0.016-0.018 on starcoder2-smoke.
"""
import pytest

import smoke_cell
from chipbench import faults
from chipbench.cell import run_cell

# cell: (configuration, traffic mix)
CELLS = {"smoke-closed": ("granite-smoke", "smoke-closed"),
         "starcoder2-smoke-closed": ("starcoder2-smoke", "smoke-closed")}
LIMIT = 0.001
SEED = 1


@pytest.fixture(scope="module")
def copies(tmp_path_factory):
    made = {}

    def get(cell):
        if cell not in made:
            root, bench, names = smoke_cell.make_copy(
                tmp_path_factory.mktemp(cell), [CELLS[cell]], limit=LIMIT)
            made[cell] = (root, bench, names[0])
        return made[cell]
    return get


def _run(cell, mutate=None, controls=()):
    root, bench, name = cell
    return run_cell(name, SEED, 1.0, False, root=root, bench_dir=bench,
                    mutate=mutate, controls=controls)[0]


@pytest.mark.parametrize("cell", ["smoke-closed", "starcoder2-smoke-closed"])
def test_sound_run_passes_and_control_fails(copies, cell):
    r = _run(copies(cell), controls=("fp8",))
    gap = r["checks"]["mean_logit_gap"]
    assert r["correct"] is True and gap["value"] <= gap["limit"]
    control = r["controls"]["ref_fp8"]
    assert control["mean"] > gap["limit"] and control["ok"] is False


@pytest.mark.parametrize("cell,fault", [
    ("smoke-closed", "state_unchanged"), ("smoke-closed", "half_batch"),
    ("smoke-closed", "token_altered")],
    ids=["closed-state_unchanged", "closed-half_batch",
         "closed-token_altered"])
def test_broken_timed_path_is_not_correct(copies, cell, fault):
    r = _run(copies(cell), mutate=faults.FAULTS[fault])
    gap = r["checks"]["mean_logit_gap"]
    assert r["correct"] is False, gap
    assert gap["value"] > gap["limit"]
