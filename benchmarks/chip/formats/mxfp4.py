"""mxfp4: OCP MX v1.0 FP4 (E2M1) codes with one power-of-two scale (UE8M0)
per ``group`` inputs.

Weights are drawn as codes, not as floats to be quantized: each of the 16
E2M1 codes uniformly (the sign bit makes the weights' mean zero), so the
subnormal codes 1 and 9 (+-0.5) make up one code in eight, and one scale
per ``group`` inputs of each output column, ``2^(e + d)`` with ``e`` the
nearest integer to ``log2(1 / (CODE_RMS * sqrt(K)))`` and ``d`` uniform
over -1, 0, 1.

The reference reads the codes by the published table (``VALUES``, written
out here, not taken from the program).  The program holds each scale as an
f32; the yardstick counts the format as published: 4 bits a code, 1 byte a
scale.

Every function takes the key of one layer of one linear and ``w``, the
file's weight format as it applies to that linear (``spec.layer_format``).
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

BITS = 4
SCALE_BYTES = {"ue8m0": 1}
# OCP MX v1.0 E2M1 magnitudes of codes 0..7; code 8 + c is minus code c
MAGNITUDES = (0.0, 0.5, 1.0, 1.5, 2.0, 3.0, 4.0, 6.0)
VALUES = np.array(MAGNITUDES + tuple(-v for v in MAGNITUDES), np.float32)

# root-mean-square code value over the codes as they are drawn
CODE_RMS = float(np.sqrt(np.mean(VALUES.astype(np.float64) ** 2)))


def check(w: Dict) -> None:
    if w["scale"] not in SCALE_BYTES:
        raise ValueError(f"no rule to draw mxfp4 weights with {w['scale']} "
                         "scales")


def draw_codes(key, k: int, n: int):
    """[K, N] int32 E2M1 codes, each of 0..15 equally likely."""
    bits = jax.random.bits(key, (k // 8, n), jnp.uint32)
    codes = jnp.stack([(bits >> (4 * i)) & 15 for i in range(8)], axis=1)
    return codes.reshape(k, n).astype(jnp.int32)


def draw_scales(key, k: int, n: int, group: int):
    """[K / group, N] f32 powers of two."""
    e = int(np.round(np.log2(1.0 / (CODE_RMS * np.sqrt(k)))))
    d = jax.random.randint(key, (k // group, n), -1, 2)
    return jnp.exp2((e + d).astype(jnp.float32))


def served(key, k: int, n: int, w: Dict):
    """The program's leaves: codes packed by its own ``pack_codes``, and
    the scales as the f32 it holds them in."""
    from repro.quant.pack import pack_codes
    codes = draw_codes(jax.random.fold_in(key, 0), k, n)
    return (pack_codes(codes, BITS),
            draw_scales(jax.random.fold_in(key, 1), k, n, w["group"]))


def dense(key, k: int, n: int, w: Dict):
    """The reference's f32 [K, N] weight: the codes read by the OCP table,
    times their scales."""
    codes = draw_codes(jax.random.fold_in(key, 0), k, n)
    scales = draw_scales(jax.random.fold_in(key, 1), k, n, w["group"])
    vals = jnp.asarray(VALUES)[codes]
    return (vals.reshape(k // w["group"], w["group"], n)
            * scales[:, None, :]).reshape(k, n)


def nbytes(k: int, n: int, w: Dict) -> int:
    """Bytes one read of the linear costs in the published format: its
    codes and its one-byte scales."""
    return k * n * BITS // 8 + k // w["group"] * n * SCALE_BYTES[w["scale"]]
