"""Serving observability (DESIGN.md §13): spans, structured tracing and a
metrics registry for the continuous-batching engine.

``span`` marks the phases of a scheduling round in the ``jax.profiler``
trace and, when a ``Tracer`` is attached, as Chrome events on the
scheduler's clock.  The ``Observability`` bundle is what the scheduler
takes (``Scheduler(engine, obs=...)``).  ``obs=None`` (the default) adds
nothing observable: the scheduler makes zero extra clock calls, zero extra
host syncs and zero extra dispatches — pinned by tests/test_obs.py.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

from .registry import (DEFAULT_TIME_BUCKETS, Counter, Gauge, Histogram,
                       MetricsRegistry, SnapshotWriter)
from .span import span
from .trace import PID_REQUESTS, PID_SCHEDULER, Tracer

__all__ = [
    "Counter", "DEFAULT_TIME_BUCKETS", "Gauge", "Histogram",
    "MetricsRegistry", "Observability", "PID_REQUESTS", "PID_SCHEDULER",
    "SnapshotWriter", "Tracer", "span",
]


@dataclasses.dataclass
class Observability:
    """What the scheduler consumes.  Any field may be None; the scheduler
    guards every hook on the specific field it needs, so e.g. a tracer
    without a registry costs nothing registry-shaped."""
    tracer: Optional[Tracer] = None
    registry: Optional[MetricsRegistry] = None
    # periodic JSONL snapshots of ``registry`` (scheduler clock timebase)
    snapshots: Optional[SnapshotWriter] = None

    def on_step(self, now: float) -> None:
        """Called by the scheduler once per step (post-round)."""
        if self.snapshots is not None:
            self.snapshots.maybe_write(now)
