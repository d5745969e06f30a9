"""Scheduler: host work per scheduling round that the device waits behind.

Mean over the traced window's ``serve.step`` spans (one per
``Scheduler.step()``) of the span less the ``serve.sync`` spans inside it
(the blocking device->host reads), in ms (``chipbench.scopes``)."""


def read(rec):
    h = (rec.get("scopes") or {}).get("host_step")
    if not h or not h["count"]:
        return None
    return 1e3 * h["seconds"] / h["count"]
