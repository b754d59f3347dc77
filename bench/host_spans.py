"""The program's own host spans (``sched.*``, ``repro.core.tracing``) in
the traced stretch of a window, and the chip's idle gaps named by them.

``for_run(run)`` gives the per-layer readers ``host_ms.groups``,
``host_ms.sync`` and ``syncs_per_step`` each span name's count and seconds
over the stretch, with its steps (``sched.finish`` spans). The harness
keeps a ``--trace 1`` run's trace in a ``bench_trace_*`` directory under
the temporary directory until its readers have run; ``for_run`` takes the
newest one whose ``bench.step`` window is the run's own (``run.trace``).
A program without these spans reads nothing.

``idle_gaps(path)`` names each idle gap of device 0 by the innermost span
(``bench.*`` or ``sched.*``) that covers most of it and the program the
device ran next, e.g. ``sched.group.profile before jit_gather``. From the
root of a checkout,

    python -m bench.host_spans <trace dir>

prints a kept trace's span table and its named idle gaps.
"""
from __future__ import annotations

import bisect
import functools
import glob
import math
import os
import sys
import tempfile
from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

from bench import trace_reduce

Span = Tuple[str, float, float]  # name, start s, end s


@dataclass
class Stretch:
    window_s: float  # first bench.step start to last bench.step end
    totals: Dict[str, Tuple[int, float]]  # sched.* name -> (count, seconds)

    @property
    def steps(self) -> int:
        return self.totals.get("sched.finish", (0, 0.0))[0]

    def seconds(self, name: str) -> float:
        return self.totals.get(name, (0, 0.0))[1]

    def syncs(self) -> Tuple[int, float]:
        """Count and seconds of the host<->device round trips (``*.sync``)."""
        v = [cs for n, cs in self.totals.items() if n.endswith(".sync")]
        return sum(c for c, _ in v), sum(s for _, s in v)


def host_spans(pd) -> List[Span]:
    return (trace_reduce._host_spans(pd, "bench.")
            + trace_reduce._host_spans(pd, "sched."))


def _window(spans: List[Span]) -> Tuple[float, float]:
    steps = [s for s in spans if s[0] == "bench.step"]
    if not steps:
        return math.nan, math.nan
    return min(s[1] for s in steps), max(s[2] for s in steps)


@functools.lru_cache(maxsize=4)
def _stretch(path: str, mtime: float) -> Stretch:
    from jax.profiler import ProfileData

    spans = host_spans(ProfileData.from_file(path))
    lo, hi = _window(spans)
    totals: Dict[str, Tuple[int, float]] = {}
    for name, a, b in spans:
        if name.startswith("sched."):
            c, s = totals.get(name, (0, 0.0))
            totals[name] = (c + 1, s + b - a)
    return Stretch(window_s=hi - lo, totals=totals)


def stretch(path: str) -> Stretch:
    return _stretch(path, os.path.getmtime(path))


def for_run(run) -> Optional[Stretch]:
    """The spans of this run's traced stretch, or None where the run has no
    trace, its trace is not found, or the program wrote no steps' spans."""
    t = getattr(run, "trace", None)
    if t is None:
        return None
    paths = glob.glob(os.path.join(tempfile.gettempdir(), "bench_trace_*",
                                   "plugins", "profile", "*", "*.xplane.pb"))
    for path in sorted(paths, key=os.path.getmtime, reverse=True):
        s = stretch(path)
        if math.isclose(s.window_s, t.window_s, rel_tol=0, abs_tol=1e-9):
            return s if s.steps else None
    return None


def gap_owner(spans: List[Span], a: float, b: float) -> str:
    """The innermost (shortest) span that covers more than half of the gap
    [a, b]; failing that, the span that covers most of it."""
    best, key = "outside host spans", None
    for name, s0, s1 in spans:
        ov = min(b, s1) - max(a, s0)
        if ov <= 0:
            continue
        k = (0, s1 - s0) if 2 * ov > b - a else (1, -ov)
        if key is None or k < key:
            best, key = name, k
    return best


def idle_gaps(path: str) -> Dict[str, float]:
    """Idle seconds of device 0 in the window, by ``<owner> before
    <next program>`` (the same gaps as ``trace_reduce``'s)."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    dev = min((pl for pl in pd.planes if pl.name.startswith("/device:TPU:")
               and trace_reduce._line(pl, "XLA Ops") is not None),
              key=lambda pl: int(pl.name.rsplit(":", 1)[1]))
    spans = host_spans(pd)
    lo, hi = _window(spans)
    ops = trace_reduce._events(trace_reduce._line(dev, "XLA Ops"))
    busy = trace_reduce._union([(max(a, lo), min(b, hi))
                                for _, a, b in ops if b > lo and a < hi])
    mline = trace_reduce._line(dev, "XLA Modules")
    mods = sorted(trace_reduce._events(mline), key=lambda e: e[1]) \
        if mline else []
    starts = [m[1] for m in mods]
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    out: Dict[str, float] = {}
    spans.sort(key=lambda s: s[1])
    live: List[Span] = []  # spans begun before the gap ends, not yet over
    i = 0
    for a, b in zip(edges[0::2], edges[1::2]):
        if b > a:
            while i < len(spans) and spans[i][1] < b:
                live.append(spans[i])
                i += 1
            live = [s for s in live if s[2] > a]
            j = bisect.bisect_left(starts, b - 1e-9)
            nxt = (trace_reduce.program_name(mods[j][0]) if j < len(mods)
                   else "-")
            k = f"{gap_owner(live, a, b)} before {nxt}"
            out[k] = out.get(k, 0.0) + (b - a)
    return out


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    path = trace_reduce.find_xplane(argv[0])
    s = stretch(path)
    n = max(s.steps, 1)
    print(f"{s.steps} steps in {s.window_s:.6f} s")
    print(f"{'span':24} {'count':>7} {'ms a step':>10}")
    for name, (c, sec) in sorted(s.totals.items(), key=lambda kv: -kv[1][1]):
        print(f"{name:24} {c:7d} {sec / n * 1e3:10.3f}")
    gaps = idle_gaps(path)
    total = sum(gaps.values())
    by_sched = sum(v for k, v in gaps.items() if k.startswith("sched."))
    print(f"idle {total:.6f} s, {100 * by_sched / max(total, 1e-12):.2f} % "
          "owned by sched.* spans")
    for k, v in sorted(gaps.items(), key=lambda kv: -kv[1])[:15]:
        print(f"{v:.6f}  {k}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
