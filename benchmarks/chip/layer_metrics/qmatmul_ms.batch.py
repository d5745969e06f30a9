"""Kernels: device time of the quantized weight products per decode
token-step.

Self seconds of the decode programs' operations under the program's
``qmatmul`` scope (jnp dequantize-and-dot or the Pallas kernel alike) in
the traced window, over the decode token-steps the program ran there, in
ms (``chipbench.scopes``)."""
from chipbench import scopes


def read(rec):
    return scopes.ms_per_token_step(rec, "qmatmul")
