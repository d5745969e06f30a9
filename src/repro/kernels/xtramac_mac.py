"""Pallas kernel: the XtraMAC virtual-DSP packed multiply (Eqs. 9-11).

Emulates the DSP48E2 27x18-bit wide multiplier on 32-bit TPU VPU lanes:
mantissa lanes are packed into the two port words (Eq. 9), ONE wide
multiply produces all lane products (Eq. 10), and shift-and-mask extracts
them (Eq. 11).  Because the 45-bit product exceeds int32, the wide multiply
is computed multiprecision:

  A = ahi*2^13 + alo,  B = bhi*2^9 + blo      (4 partials, each <= 2^23)
  P = p00 + p01*2^9 + p10*2^13 + p11*2^22     accumulated into 16-bit limbs

Lane extraction reads a <=17-bit window from at most two adjacent limbs at
the statically-known lane position.  Validated bit-exactly against the
int64 oracle in core.packing across every paper datatype combination and
randomized magnitudes (tests/test_kernels.py).

This kernel is the microarchitecture-fidelity artifact; the *throughput*
kernels for LLM inference are in packed_matmul.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.packing import LanePlan
from repro.kernels import resolve_interpret


def _wide_multiply_limbs(a, b):
    """27-bit x 18-bit -> three 16-bit limbs (int32 arrays, no overflow)."""
    alo = a & 0x1FFF          # 13 bits
    ahi = a >> 13             # <= 14 bits
    blo = b & 0x1FF           # 9 bits
    bhi = b >> 9              # <= 9 bits
    p00 = alo * blo           # <= 22 bits
    p01 = alo * bhi           # <= 22 bits, weight 2^9
    p10 = ahi * blo           # <= 23 bits, weight 2^13
    p11 = ahi * bhi           # <= 23 bits, weight 2^22

    l0 = (p00 & 0xFFFF) + ((p01 & 0x7F) << 9) + ((p10 & 0x7) << 13)
    l1 = (p00 >> 16) + (p01 >> 7) + (p10 >> 3) + ((p11 & 0x3FF) << 6)
    l2 = p11 >> 10
    # carry normalization to 16-bit limbs
    l1 = l1 + (l0 >> 16)
    l0 = l0 & 0xFFFF
    l2 = l2 + (l1 >> 16)
    l1 = l1 & 0xFFFF
    return l0, l1, l2


def _extract_lane(limbs, pos: int, width: int):
    """Static shift-and-mask window [pos, pos+width) over the limb triple.

    Widths up to 19 bits can span three 16-bit limbs (e.g. INT8xFP16 lanes,
    stride 19, at offset r=15).  All shifts are int32-safe: each partial is
    < 2^width <= 2^19."""
    assert width <= 19 and pos + width <= 48
    j, r = divmod(pos, 16)
    out = limbs[j] >> r
    need1 = max(0, width - (16 - r))
    if need1 > 0 and j + 1 < len(limbs):
        out = out | ((limbs[j + 1] & ((1 << min(need1, 16)) - 1)) << (16 - r))
    need2 = max(0, width - (32 - r))
    if need2 > 0 and j + 2 < len(limbs):
        out = out | ((limbs[j + 2] & ((1 << need2) - 1)) << (32 - r))
    return out & ((1 << width) - 1)


def _vdsp_kernel(a_ref, b_ref, o_ref, *, plan: LanePlan):
    # Eq. 9: pack each port's lanes at their static offsets
    a_word = jnp.zeros_like(a_ref[:, 0])
    for i, off in enumerate(plan.offsets_a):
        a_word = a_word | (a_ref[:, i] << off)
    b_word = jnp.zeros_like(b_ref[:, 0])
    for j, off in enumerate(plan.offsets_b):
        b_word = b_word | (b_ref[:, j] << off)
    # Eq. 10: ONE wide multiply (multiprecision on int32)
    limbs = _wide_multiply_limbs(a_word, b_word)
    # Eq. 11: static shift-and-mask extraction per lane
    for lane, (_, _, pos) in enumerate(plan.lane_positions):
        o_ref[:, lane] = _extract_lane(limbs, pos, plan.stride)


@functools.partial(jax.jit, static_argnames=("plan", "bt", "interpret"))
def _vdsp_impl(a_mags, b_mags, *, plan: LanePlan, bt: int, interpret: bool):
    t = a_mags.shape[0]
    n_a, n_b = len(plan.offsets_a), len(plan.offsets_b)
    return pl.pallas_call(
        functools.partial(_vdsp_kernel, plan=plan),
        grid=(t // bt,),
        in_specs=[
            pl.BlockSpec((bt, n_a), lambda i: (i, 0)),
            pl.BlockSpec((bt, n_b), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((bt, plan.parallelism), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((t, plan.parallelism), jnp.int32),
        interpret=interpret,
        name="xtramac_mac",
    )(a_mags, b_mags)


def virtual_dsp_multiply(a_mags, b_mags, plan: LanePlan, *, bt: int = 1024,
                         interpret=None):
    """Packed lane products [T, P] from magnitudes [T, n_a] x [T, n_b]."""
    t = a_mags.shape[0]
    bt = min(bt, t)
    assert t % bt == 0, (t, bt)
    return _vdsp_impl(jnp.asarray(a_mags, jnp.int32), jnp.asarray(b_mags, jnp.int32),
                      plan=plan, bt=bt, interpret=resolve_interpret(interpret))
