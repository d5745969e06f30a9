"""Model step: device time per prefill chunk.

Device seconds of the ``jit_prefill_chunk`` programs in the traced window
over their executions there."""
from chipbench import trace


def read(rec):
    red = rec.get("trace")
    if not red:
        return None
    secs, runs = trace.program_seconds(red, ("jit_prefill_chunk",))
    return 1e3 * secs / runs if runs else None
