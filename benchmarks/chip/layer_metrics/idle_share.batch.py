"""Device: share of the traced window in which no operation ran.

1 - (union of the device's operation intervals / window), from the
profiler trace (``chipbench.trace.reduce``)."""


def read(rec):
    red = rec.get("trace")
    if not red or not red["window_s"]:
        return None
    return 100.0 * (1.0 - red["busy_s"] / red["window_s"])
