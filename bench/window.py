"""The window's own copy of ``Scheduler.run``'s loop, with a deadline.

It drives the program only through the scheduler's public calls —
``normalize``, ``try_admit``, ``step``, ``drain_waiting`` and
``drain_finished`` — the way the replica router does. Requests are handed
over when they are due (an open loop: the generator never waits for the
server); how late it hands them over is recorded. Each step is recorded
with the work it needed (``flops.StepRecord``).
"""
from __future__ import annotations

import contextlib
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional

from bench.flops import StepRecord


@dataclass
class WindowResult:
    requests: List[Any]  # ServeRequests handed over (due order)
    steps: List[StepRecord]  # steps that started inside the window
    lateness: List[float]  # hand-over time - due time, per request
    trace_steps: List[StepRecord] = field(default_factory=list)
    followed_s: float = 0.0  # time spent after the window closed


def _pre_step(sched):
    pos = [st.kv_len for st in sched.active.values()]
    pos += [g.kv_len for g in sched.groups.values() for _ in g.slots]
    cur = {}
    if sched.chunk_mgr is not None:
        cur = {s: (c.pos, c.n_prompt, c.req)
               for s, c in sched.chunk_mgr.cursors.items()}
    return pos, cur, sched.n_mixed_steps


def _record(sched, pre, t0: float, t1: float) -> StepRecord:
    pos, cur, n_mixed = pre
    rec = StepRecord(
        kind="mixed" if sched.n_mixed_steps > n_mixed else "decode",
        t0=t0, t1=t1, decode_pos=pos,
        occupancy=sched.occupancy_trace[-1] if sched.occupancy_trace else 0.0,
        block_use=(sched.block_occupancy_trace[-1]
                   if sched.block_occupancy_trace else 0.0),
    )
    if rec.kind == "mixed":
        now = sched.chunk_mgr.cursors
        for s, (p0, n_prompt, req) in cur.items():
            if s in now and now[s].req is req:
                if now[s].pos > p0:
                    rec.chunks.append((p0, now[s].pos - p0))
            elif req.tokens:  # the prompt's last chunk ran: first token out
                rec.chunks.append((p0, n_prompt - p0))
                rec.n_first += 1
            # else: preempted before its chunk ran
    return rec


def drive(sched, requests: List[Any], seconds: float, *, open_loop: bool,
          clock: Callable[[], float] = time.perf_counter,
          annotate: Callable[[str], Any] = lambda name: contextlib.nullcontext(),
          follow_s: float = 60.0, max_steps: Optional[int] = None,
          trace: Optional[Dict[str, Any]] = None) -> WindowResult:
    """Serve ``requests`` (ServeRequests whose ``t_arrival`` is their due
    time) for ``seconds``. An open loop then keeps serving, without new
    arrivals, until every request due in the window has its first token
    (at most ``follow_s`` more). ``trace`` holds ``begin`` and ``end``
    callables, ``start`` and ``length`` in seconds and an optional
    ``ready(sched)`` predicate: the profiler runs from the first step
    boundary after ``start`` at which ``ready`` holds, for ``length``
    seconds."""
    pending = deque(sorted(requests, key=lambda r: r.t_arrival))
    queue: deque = deque()
    handed: List[Any] = []
    lateness: List[float] = []
    steps: List[StepRecord] = []
    trace_steps: List[StepRecord] = []
    tracing = False
    n_steps = 0
    t0 = clock()
    sched.rebase(t0)
    while True:
        now = clock() - t0
        in_window = now < seconds
        with annotate("bench.generator"):
            while pending and pending[0].t_arrival <= now \
                    and pending[0].t_arrival < seconds:
                r = pending.popleft()
                lateness.append(now - r.t_arrival)
                queue.append(r)
                handed.append(r)
        if not in_window:
            if not open_loop:
                break
            waiting = [r for r in handed if r.t_first is None]
            if not waiting or now > seconds + follow_s:
                break
        with annotate("bench.admit"):
            while queue and sched.try_admit(queue[0], now):
                queue.popleft()
        if trace is not None and in_window:
            ready = trace.get("ready", lambda sc: True)
            if (not tracing and not trace.get("done")
                    and now >= trace["start"] and ready(sched)):
                trace["begin"]()
                tracing, trace["t"] = True, now
            elif tracing and now >= trace["t"] + trace["length"]:
                trace["end"]()
                tracing, trace["done"] = False, True
        if not sched.has_work:
            nxt = pending[0].t_arrival - (clock() - t0) if pending else 1e-3
            time.sleep(min(max(nxt, 0.0), 1e-3))
            continue
        pre = _pre_step(sched)
        s0 = clock() - t0
        with annotate("bench.step"):
            sched.step()
        s1 = clock() - t0
        rec = _record(sched, pre, s0, s1)
        if s0 < seconds:
            steps.append(rec)
            if tracing:
                trace_steps.append(rec)
        with annotate("bench.drain"):
            queue.extendleft(reversed(sched.drain_waiting()))
            sched.drain_finished()
        n_steps += 1
        if max_steps is not None and n_steps >= max_steps:
            break
    if tracing:
        trace["end"]()
        trace["done"] = True
    return WindowResult(requests=handed, steps=steps, lateness=lateness,
                        trace_steps=trace_steps,
                        followed_s=max(0.0, clock() - t0 - seconds))
