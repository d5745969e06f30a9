"""The MX FP4 weight format's rules (``formats/mxfp4.py``).

Every one of the 16 E2M1 codes is drawn, as often as the others; the
program's leaves (``served``) and the reference's floats (``dense``) are
the same weights, read through the program's own ``dequantize``; scales
are powers of two; one read costs what the published format holds, half a
byte a weight and a byte per 32 weights.
"""
import jax
import numpy as np
import pytest

import smoke_cell
from chipbench import spec

from repro.quant.pack import unpack_codes
from repro.quant.schemes import (QuantizedLinearWeights, dequantize,
                                 get_scheme)

MX = spec.load_module("formats", "mxfp4", smoke_cell.BENCH)
W = {"format": "mxfp4", "group": 32, "scale": "ue8m0"}
KEY = jax.random.PRNGKey(2**31 + 7)


def test_every_code_is_drawn_alike():
    codes = np.asarray(MX.draw_codes(KEY, 1024, 256))
    counts = np.bincount(codes.ravel(), minlength=16)
    assert counts.shape == (16,) and counts.min() > 0
    # 262,144 draws: each code's count within 5% of a sixteenth
    assert np.all(np.abs(counts / codes.size - 1 / 16) < 0.05 / 16)
    assert MX.VALUES[1] == 0.5 and MX.VALUES[9] == -0.5


@pytest.mark.parametrize("k,n", [(64, 128), (6144, 512)])
def test_served_and_dense_are_the_same_weights(k, n):
    packed, scales = MX.served(KEY, k, n, W)
    assert packed.shape == (k // 8, n) and scales.shape == (k // 32, n)
    qw = QuantizedLinearWeights(get_scheme("mxfp4"), packed, scales, (k, n))
    np.testing.assert_array_equal(np.asarray(dequantize(qw, np.float32)),
                                  np.asarray(MX.dense(KEY, k, n, W)))
    np.testing.assert_array_equal(np.asarray(unpack_codes(packed, 4)),
                                  np.asarray(MX.draw_codes(
                                      jax.random.fold_in(KEY, 0), k, n)))
    e = np.log2(np.asarray(scales))
    assert np.all(e == np.round(e))
    center = np.round(np.log2(1 / (MX.CODE_RMS * np.sqrt(k))))
    assert set(np.unique(e - center)) == {-1.0, 0.0, 1.0}


def test_bytes_are_the_published_format():
    assert MX.nbytes(6144, 24576, W) == 6144 * 24576 // 2 \
        + 6144 * 24576 // 32
    with pytest.raises(ValueError):
        MX.check(dict(W, scale="f32"))
