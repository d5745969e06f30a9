"""Model step: device time per decode token-step.

Device seconds of the decode programs (``jit_decode_slots``,
``jit_decode_burst``) in the traced window over the decode token-steps the
program ran there."""
from chipbench import records, trace


def read(rec):
    red = rec.get("trace")
    if not red:
        return None
    secs, runs = trace.program_seconds(red, ("jit_decode_slots",
                                             "jit_decode_burst"))
    token_steps = sum(s["token_steps"] for s in records.window_steps(rec))
    if not runs or not token_steps:
        return None
    return 1e3 * secs / token_steps
