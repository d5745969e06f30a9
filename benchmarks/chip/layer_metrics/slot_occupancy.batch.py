"""Scheduler: share of the pool's slot-steps that delivered a token.

Decode tokens delivered in the window over n_slots times the decode
token-steps the program ran (a burst of K counts K; ``ServeMetrics``'
``decode_token_steps``).  Read from ``Scheduler.step()``'s returns."""
from chipbench import records


def read(rec):
    steps = records.window_steps(rec)
    token_steps = sum(s["token_steps"] for s in steps)
    if not token_steps:
        return None
    decoded = sum(len(r) for r in records.decode_substeps(rec))
    return 100.0 * decoded / (rec["n_slots"] * token_steps)
