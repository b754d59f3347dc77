"""Named host spans for the serving loop.

``Spans`` is a per-scheduler recorder. ``spans("sched.finish", **meta)``
returns a context manager that does two things:

- writes a ``jax.profiler.TraceAnnotation`` (metadata as its stats) into
  the profiler's trace when one is running, on the device trace's clock,
  so an operator sees where each idle gap of the chip sits in the host
  step;
- adds its wall time, on the recorder's clock, to per-name totals:
  ``count``, ``seconds`` and ``self_seconds`` (the duration less the time
  its child spans cover).

Spans are always on: outside a trace one costs about a microsecond.
Names are fixed (readers and PERF.md key on them); every host<->device
round trip sits in a span whose name ends in ``.sync``, so the count of
round trips is the count of those spans.
"""
from __future__ import annotations

import time
from typing import Dict, List

import jax


class Spans:
    """Per-name totals of named, nested host spans (see the module doc)."""

    def __init__(self, clock=time.perf_counter):
        self.clock = clock
        self._totals: Dict[str, List[float]] = {}  # [count, seconds, self]
        self._open: List[float] = []  # child seconds of each open span

    def __call__(self, name: str, **meta) -> "_Span":
        return _Span(self, name, jax.profiler.TraceAnnotation(name, **meta))

    def step(self, name: str, step_num: int) -> "_Span":
        """A span that XProf's step view also reads as step ``step_num``."""
        return _Span(self, name,
                     jax.profiler.StepTraceAnnotation(name, step_num=step_num))

    def totals(self) -> Dict[str, Dict[str, float]]:
        return {n: {"count": int(c), "seconds": s, "self_seconds": own}
                for n, (c, s, own) in self._totals.items()}


class _Span:
    """One span; a plain class, since it sits on the per-step hot path."""

    __slots__ = ("rec", "name", "ann", "t0")

    def __init__(self, rec: Spans, name: str, ann):
        self.rec, self.name, self.ann = rec, name, ann

    def __enter__(self) -> "_Span":
        self.ann.__enter__()
        self.rec._open.append(0.0)
        self.t0 = self.rec.clock()
        return self

    def __exit__(self, *exc) -> bool:
        rec = self.rec
        dt = rec.clock() - self.t0
        child = rec._open.pop()
        if rec._open:
            rec._open[-1] += dt
        tot = rec._totals.get(self.name)
        if tot is None:
            tot = rec._totals[self.name] = [0, 0.0, 0.0]
        tot[0] += 1
        tot[1] += dt
        tot[2] += dt - child
        self.ann.__exit__(*exc)
        return False
