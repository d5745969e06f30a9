"""int4: 4-bit integer codes with one f32 scale per ``group`` inputs.

Weights are drawn as codes, not as floats to be quantized: codes read as
two's complement, uniform over -7..7 with zero taking the place of -8 (so
the weights have mean zero: a lopsided code range adds a common offset to
every output, and over 36 layers the residual stream collapses onto one
direction), and one f32 scale per ``group`` inputs of each output column,
``2^u / (CODE_RMS * sqrt(K))`` with ``u`` uniform over [-0.5, 0.5).

Every function takes the key of one layer of one linear and ``w``, the
file's weight format as it applies to that linear (``spec.layer_format``).
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

BITS = 4
SCALE_BYTES = {"f32": 4}

# root-mean-square code value, which sets the scale for an N(0, 1/K)-sized
# weight, over the codes as they are drawn
CODE_RMS = float(np.sqrt(np.mean(np.where(
    np.arange(-8, 8) == -8, 0, np.arange(-8, 8)) ** 2.0)))


def check(w: Dict) -> None:
    if w["scale"] not in SCALE_BYTES:
        raise ValueError(f"no rule to draw int4 weights with {w['scale']} "
                         "scales")


def draw_codes(key, k: int, n: int):
    """[K, N] int32 int4 codes 0..15 (two's complement, never 8)."""
    bits = jax.random.bits(key, (k // 8, n), jnp.uint32)
    codes = jnp.stack([(bits >> (4 * i)) & 15 for i in range(8)], axis=1)
    codes = codes.reshape(k, n).astype(jnp.int32)
    return jnp.where(codes == 8, 0, codes)


def draw_scales(key, k: int, n: int, group: int):
    """[K / group, N] f32 scales."""
    target = 1.0 / (CODE_RMS * np.sqrt(k))
    u = jax.random.uniform(key, (k // group, n), jnp.float32, -0.5, 0.5)
    return (target * jnp.exp2(u)).astype(jnp.float32)


def served(key, k: int, n: int, w: Dict):
    """The program's leaves: codes packed by its own ``pack_codes``, and
    the f32 scales."""
    from repro.quant.pack import pack_codes
    codes = draw_codes(jax.random.fold_in(key, 0), k, n)
    return (pack_codes(codes, BITS),
            draw_scales(jax.random.fold_in(key, 1), k, n, w["group"]))


def dense(key, k: int, n: int, w: Dict):
    """The reference's f32 [K, N] weight: the codes read plainly as two's
    complement, times their scales."""
    codes = draw_codes(jax.random.fold_in(key, 0), k, n)
    scales = draw_scales(jax.random.fold_in(key, 1), k, n, w["group"])
    vals = jnp.where(codes >= 8, codes - 16, codes).astype(jnp.float32)
    return (vals.reshape(k // w["group"], w["group"], n)
            * scales[:, None, :]).reshape(k, n)


def nbytes(k: int, n: int, w: Dict) -> int:
    """Bytes one read of the linear costs: its codes and its scales."""
    return k * n * BITS // 8 + k // w["group"] * n * SCALE_BYTES[w["scale"]]
