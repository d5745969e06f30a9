"""Model step: device time of the blocks' own elementwise work per decode
token-step.

Self seconds of the decode programs' operations whose innermost known scope
is ``attn`` or ``ffn`` (the norms, bias adds, RoPE, the FFN activation and
the reshapes between products; not ``qmatmul``, ``attn_core`` or
``kv_write``) in the traced window, over the decode token-steps the program
ran there, in ms (``chipbench.scopes``)."""
from chipbench import scopes


def read(rec):
    parts = [scopes.ms_per_token_step(rec, s) for s in ("attn", "ffn")]
    parts = [p for p in parts if p is not None]
    return sum(parts) if parts else None
