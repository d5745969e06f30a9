"""One span helper for the serving loop, with two sinks (DESIGN.md §13).

``span(name, **args)`` marks one phase of a scheduling round (``serve.step``,
``serve.admit``, ``serve.prefill``, ``serve.decode``, ``serve.sync``,
``serve.emit``; docs/observability.md lists them):

* it always enters ``jax.profiler.TraceAnnotation``, so under a
  ``jax.profiler`` session the span lands in the profiler trace on the
  device's clock, beside the device operations it waited for.  With no
  session the enter is a native no-op that reads no clock.
* given a ``Tracer`` and the scheduler's clock, it also writes the Chrome
  'X' event ``event`` on that clock (the dispatch events ``prefill_chunk``,
  ``decode_burst``, ``spec_draft``, ``spec_verify``).  Only then is the
  clock read, so a scheduler without a tracer makes no extra clock call,
  and two virtual-clock runs still write byte-identical Chrome files.

Args are counts at the span's boundaries: those given when it opens, and
those ``set()`` before it closes (tokens emitted, requests finished).  The
profiler gets the scalar ones; the Chrome event gets all of them.
"""
from __future__ import annotations

from typing import Callable, Dict, Optional

import jax

from .trace import Tracer

_SCALAR = (bool, int, float, str)


class span:
    """Context manager for one span; see the module docstring.

    ``step``: the round number, for the round's own span — it then enters
    ``StepTraceAnnotation`` (the profiler's step marker)."""

    __slots__ = ("name", "args", "tracer", "clock", "event", "pid", "tid",
                 "step", "_ann", "_t0")

    def __init__(self, name: str, tracer: Optional[Tracer] = None,
                 clock: Optional[Callable[[], float]] = None, *,
                 event: Optional[str] = None, pid: int = 0, tid: int = 0,
                 step: Optional[int] = None, **args):
        self.name = name
        self.args: Dict = args
        self.tracer = tracer if event is not None else None
        self.clock = clock
        self.event = event
        self.pid = pid
        self.tid = tid
        self.step = step

    def __enter__(self) -> "span":
        if self.step is not None:
            self._ann = jax.profiler.StepTraceAnnotation(
                self.name, step_num=self.step)
        else:
            self._ann = jax.profiler.TraceAnnotation(self.name, **{
                k: v for k, v in self.args.items() if isinstance(v, _SCALAR)})
        self._ann.__enter__()
        if self.tracer is not None:
            self._t0 = self.clock()
        return self

    def set(self, **args) -> None:
        """Add args known only at the end of the span."""
        self.args.update(args)
        self._ann.set_metadata(**{k: v for k, v in args.items()
                                  if isinstance(v, _SCALAR)})

    def __exit__(self, *exc) -> None:
        if self.tracer is not None:
            self.tracer.complete(self.event, self._t0, self.clock(),
                                 pid=self.pid, tid=self.tid, args=self.args)
        self._ann.__exit__(*exc)
