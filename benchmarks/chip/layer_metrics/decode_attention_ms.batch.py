"""Kernels: device time of decode attention per decode token-step.

Self seconds of the decode programs' operations under the program's
``attn_core`` scope (KV cache read, scores, softmax, PV; einsum path or the
Pallas decode kernel; the KV write excluded) in the traced window, over the
decode token-steps the program ran there, in ms (``chipbench.scopes``)."""
from chipbench import scopes


def read(rec):
    return scopes.ms_per_token_step(rec, "attn_core")
