"""starcoder2-15b's block against its plain reference, on the CPU.

A cut-down configuration with the published block (LayerNorm with bias,
a bias on every linear, rope_theta 1e5, plain GELU-tanh FFN) and MX FP4
weights at group 32 (``data/configs/starcoder2-mx-smoke.json``) is served
through the whole run and compared with ``references/starcoder2.py``.
Sound runs pass the check and the control (the reference with FP8
activations) fails it; so does the program with its bias adds removed,
with its LayerNorm betas zeroed, or with the old denormals-as-zero E2M1
decode, each planted by a patch of the program for the run.

Readings on the CPU (1 s windows, every finished request compared), mean
gap below the reference's best logit: sound runs 0.000098-0.000147 over
seeds 1-3, the control 0.0032-0.0036; no bias adds 0.070-0.096, betas
zeroed 0.035-0.061, the flushed E2M1 codes 0.0105-0.0122 (seeds 1-2).
The limit, 0.001, lies between the sound runs and the control, as the
chip cell's does.
"""
from unittest import mock

import jax.numpy as jnp
import numpy as np
import pytest

import smoke_cell
from chipbench.cell import run_cell

CELL = ("starcoder2-mx-smoke", "smoke-closed")
LIMIT = 0.001


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    root, bench, names = smoke_cell.make_copy(
        tmp_path_factory.mktemp("starcoder2"), [CELL], limit=LIMIT)
    return root, bench, names[0]


def _run(copy, seed, controls=()):
    root, bench, name = copy
    return run_cell(name, seed, 1.0, False, root=root, bench_dir=bench,
                    controls=controls)[0]


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_sound_runs_pass_and_the_control_fails(copy, seed):
    r = _run(copy, seed, controls=("fp8",))
    gap = r["checks"]["mean_logit_gap"]
    assert r["correct"] is True and r["failed"] == 0, r["checks"]
    assert gap["value"] <= gap["limit"]
    control = r["controls"]["ref_fp8"]
    assert control["mean"] > gap["limit"] and control["ok"] is False


def _no_bias():
    from repro.models import attention, common, transformer
    orig = common.apply_linear

    def unbiased(leaf, x, out_dtype=jnp.bfloat16, bias=None):
        return orig(leaf, x, out_dtype)
    return [mock.patch.object(m, "apply_linear", unbiased)
            for m in (attention, transformer)]


def _beta_zeroed():
    from repro.models import transformer
    orig = transformer.layer_norm
    return [mock.patch.object(transformer, "layer_norm",
                              lambda x, g, b, eps=1e-5: orig(x, g, 0 * b,
                                                             eps))]


def _e2m1_flushed():
    """Codes 1 and 9 read as zero, as the FPGA MAC model's DAZ rule reads
    them, in the jnp decode (the CPU's path) and the kernel's."""
    from repro.kernels import packed_matmul
    from repro.quant import schemes
    daz = np.where(np.isin(np.arange(16), (1, 9)), 0.0,
                   schemes.FP4_LUT).astype(np.float32)
    orig = packed_matmul._decode_fp4_e2m1

    def kernel_daz(codes):
        return jnp.where((codes & 7) == 1, 0.0, orig(codes))
    return [mock.patch.object(schemes, "FP4_LUT", daz),
            mock.patch.object(packed_matmul, "_decode_fp4_e2m1", kernel_daz)]


@pytest.mark.parametrize("fault", [_no_bias, _beta_zeroed, _e2m1_flushed],
                         ids=["no_bias", "beta_zeroed", "e2m1_flushed"])
def test_a_program_missing_part_of_the_block_is_not_correct(copy, fault):
    patches = fault()
    for p in patches:
        p.start()
    try:
        r = _run(copy, 1)
    finally:
        for p in patches:
            p.stop()
    gap = r["checks"]["mean_logit_gap"]
    assert r["correct"] is False
    assert gap["value"] > gap["limit"]
