"""Reduce a profiler trace (``*.xplane.pb``) of the traced stretch of a
window to the numbers the per-layer metrics read.

Device planes are ``/device:TPU:<i>``; on each, the ``XLA Ops`` line holds
every operation the chip ran (named by its HLO text) and the ``XLA
Modules`` line every program (``jit_<name>(<hash>)``); an op belongs to
the program whose interval holds it. A Pallas kernel is an op whose HLO
is a ``tpu_custom_call``. The host plane ``/host:CPU`` holds the
benchmark's own ``TraceAnnotation`` spans (``bench.*``), on the same
clock. The window is from the start of the first ``bench.step`` span to
the end of the last. Each idle gap of device 0 is named by the ``bench.*``
span that covers most of it and the program the device ran next (what it
waited for), e.g. ``bench.step before jit_gather``.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from dataclasses import dataclass, field
from typing import Dict, List, Tuple

Interval = Tuple[float, float]


@dataclass
class TraceSummary:
    n_devices: int
    window_s: float
    busy_s: float  # mean over devices of the union of op intervals
    modules: Dict[str, Tuple[float, int]]  # program -> (seconds, count), device 0
    ops: Dict[str, float]  # "program:op" -> seconds, summed over devices
    idle_by_host: Dict[str, float] = field(default_factory=dict)

    def module_s(self, needle: str) -> Tuple[float, int]:
        """(seconds, count) of programs whose name contains ``needle``."""
        s = [v for n, v in self.modules.items() if needle in n]
        return sum(v[0] for v in s), sum(v[1] for v in s)


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def _events(line) -> List[Tuple[str, float, float]]:
    return [(e.name, e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9)
            for e in line.events]


def _line(plane, name):
    for ln in plane.lines:
        if ln.name == name:
            return ln
    return None


def _union(iv: List[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for a, b in sorted(iv):
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def _covered(iv: List[Interval]) -> float:
    return sum(b - a for a, b in iv)


def program_name(module: str) -> str:
    """``jit_decode_step(1234)`` -> ``jit_decode_step``."""
    return module.split("(")[0]


def op_name(hlo: str) -> str:
    """A short, stable name for an op from its HLO text: the instruction's
    name without its number, and a fusion's kind (``%fusion[kOutput]``)."""
    name = re.sub(r"\.\d+$", "", hlo.split(" = ")[0])
    kind = re.search(r"kind=(k\w+)", hlo)
    if "custom_call_target=\"tpu_custom_call\"" in hlo:
        return name + "[pallas]"
    return name + (f"[{kind.group(1)}]" if kind else "")


def _owners(modules, ops):
    """The program that holds each op (by start time), or ``-``."""
    starts = [a for _, a, _ in modules]
    out = []
    for _, a, _ in ops:
        i = bisect.bisect_right(starts, a) - 1
        ok = i >= 0 and modules[i][2] >= a
        out.append(program_name(modules[i][0]) if ok else "-")
    return out


def _host_spans(pd, prefix: str) -> List[Tuple[str, float, float]]:
    spans = []
    for pl in pd.planes:
        if pl.name.startswith("/host:"):
            for ln in pl.lines:
                spans += [e for e in _events(ln) if e[0].startswith(prefix)]
    return spans


def _gap_owner(spans, a: float, b: float) -> str:
    """The ``bench.*`` span that covers most of the gap [a, b]."""
    best, best_s = "outside bench spans", 0.0
    for name, s0, s1 in spans:
        ov = min(b, s1) - max(a, s0)
        if ov > best_s:
            best, best_s = name, ov
    return best


def reduce_trace(path: str, prefix: str = "bench.") -> TraceSummary:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devs = sorted((pl for pl in pd.planes if pl.name.startswith("/device:TPU:")
                   and _line(pl, "XLA Ops") is not None),
                  key=lambda pl: int(pl.name.rsplit(":", 1)[1]))
    if not devs:
        raise ValueError(f"no TPU device plane with an 'XLA Ops' line in {path}")
    spans = _host_spans(pd, prefix)
    steps = [s for s in spans if s[0] == prefix + "step"]
    if steps:
        lo, hi = min(s[1] for s in steps), max(s[2] for s in steps)
    else:  # no host spans: the device's own first and last op
        ev = _events(_line(devs[0], "XLA Ops"))
        lo, hi = min(e[1] for e in ev), max(e[2] for e in ev)
    busy = []
    ops: Dict[str, float] = {}
    idle: Dict[str, float] = {}
    for i, pl in enumerate(devs):
        ev = [(n, max(a, lo), min(b, hi)) for n, a, b in _events(_line(pl, "XLA Ops"))
              if b > lo and a < hi]
        mline = _line(pl, "XLA Modules")
        mods = sorted(_events(mline), key=lambda e: e[1]) if mline else []
        u = _union([(a, b) for _, a, b in ev])
        busy.append(_covered(u))
        for (n, a, b), prog in zip(ev, _owners(mods, ev)):
            k = f"{prog}:{op_name(n)}"
            ops[k] = ops.get(k, 0.0) + (b - a)
        if i == 0:  # idle gaps: what the host did, and what came next
            starts = [m[1] for m in mods]
            edges = [lo] + [x for iv in u for x in iv] + [hi]
            for a, b in zip(edges[0::2], edges[1::2]):
                if b > a:
                    j = bisect.bisect_left(starts, b - 1e-9)
                    nxt = program_name(mods[j][0]) if j < len(mods) else "-"
                    k = f"{_gap_owner(spans, a, b)} before {nxt}"
                    idle[k] = idle.get(k, 0.0) + (b - a)
    modules: Dict[str, Tuple[float, int]] = {}
    mline = _line(devs[0], "XLA Modules")
    if mline is not None:
        for n, a, b in _events(mline):
            if b > lo and a < hi:
                s, k = modules.get(n, (0.0, 0))
                modules[n] = (s + min(b, hi) - max(a, lo), k + 1)
    nd = len(devs)
    return TraceSummary(
        n_devices=nd, window_s=hi - lo, busy_s=sum(busy) / nd,
        modules=modules, ops=ops, idle_by_host=idle,
    )


def breakdown(ts: TraceSummary, n: int = 10) -> Dict[str, list]:
    """The contract's ``breakdown``: the device operations that took most
    time (mean seconds a device) and idle time by what the host did."""
    top = sorted(ts.ops.items(), key=lambda kv: -kv[1])[:n]
    gaps = sorted(ts.idle_by_host.items(), key=lambda kv: -kv[1])[:n]
    return {"device_ops": [[k, v / ts.n_devices] for k, v in top],
            "idle_gaps": [[k, v] for k, v in gaps]}
