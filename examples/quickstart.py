"""Quickstart: the XtraMAC core in 60 seconds.

Shows the paper's three key mechanisms on real numbers:
  1. the unified mantissa-product MAC (bit-exact mixed-precision arithmetic)
  2. lane packing — 2 INT4xBF16 MACs through ONE virtual-DSP multiply
  3. a quantized GEMV through the Pallas kernel vs its jnp oracle

Run:  python examples/quickstart.py
(the script puts src/ on sys.path itself — no PYTHONPATH needed)
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import numpy as np

from repro.core import formats as F
from repro.core.mac import MacConfig, xtramac
from repro.core.packing import (packed_multiply, per_lane_reference,
                                solve_lane_plan, xtramac_packed)

# -- 1. mixed-precision MAC: INT4 x BF16 + BF16 -> BF16 ----------------------
cfg = MacConfig.make("int4", "bf16", "bf16", "bf16")
a = np.array([0b0011])                                # INT4 code for +3
b = F.quantize_f64(F.BF16, np.array([1.5]))           # BF16(1.5)
c = F.quantize_f64(F.BF16, np.array([0.25]))          # BF16(0.25)
p = xtramac(cfg, a, b, c)
print("XtraMAC  3 * 1.5 + 0.25 =", F.BF16.decode_to_f64(p)[0], "(expect 4.75)")

# -- 2. lane packing: P parallel MACs in ONE integer multiply ----------------
plan = solve_lane_plan("int4", "bf16", max_parallelism=4)
print(f"\nlane plan INT4xBF16: P={plan.parallelism}, stride={plan.stride}, "
      f"offsets A={plan.offsets_a} B={plan.offsets_b}, "
      f"DSP util {plan.dsp_utilization:.1%}")
rng = np.random.default_rng(0)
a_bits = rng.integers(0, 16, (5, len(plan.offsets_a)))
b_bits = F.quantize_f64(F.BF16, rng.normal(size=(5, len(plan.offsets_b))))
c_bits = F.quantize_f64(F.BF16, rng.normal(size=(5, plan.parallelism)))
packed = xtramac_packed(cfg, plan, a_bits, b_bits, c_bits)
ref = per_lane_reference(cfg, plan, a_bits, b_bits, c_bits)
assert (packed == ref).all()
print("packed path bit-exact vs per-lane MACs over",
      packed.size, "results  [OK]")

# -- 3. quantized GEMV: packed INT4 weights through the Pallas kernel --------
import jax.numpy as jnp
from repro.kernels.packed_matmul import packed_matmul
from repro.kernels.ref import packed_matmul_ref
from repro.quant.schemes import get_scheme, quantize_weights

w = rng.standard_normal((256, 128)).astype(np.float32) * 0.1
qw = quantize_weights(get_scheme("awq_int4"), w)
x = jnp.asarray(rng.standard_normal((4, 256)), jnp.bfloat16)
out_kernel = packed_matmul(x, qw, bm=4, bn=128, bk=256)
out_ref = packed_matmul_ref(x, qw)
err = float(jnp.max(jnp.abs(out_kernel - out_ref)))
print(f"\npacked GEMV kernel vs oracle: max abs err {err:.2e}  "
      f"(weights: {qw.packed.dtype} {qw.packed.shape}, "
      f"{32 // get_scheme('awq_int4').weight_bits} codes/word)")
assert err < 1e-4
print("quickstart complete.")
