"""KV pool: device time of the KV cache writes per decode token-step.

Self seconds of the decode programs' operations under the program's
``kv_write`` scope (every write of new K/V rows into the pool) in the
traced window, over the decode token-steps the program ran there, in ms
(``chipbench.scopes``)."""
from chipbench import scopes


def read(rec):
    return scopes.ms_per_token_step(rec, "kv_write")
