"""Chunk packer: host time of one mixed step's packing (the ``sched.plan``
span: ``ChunkedPrefill.plan`` and the blocks its chunks need), over the
traced stretch, in ms. A program without the span reads nothing."""
from bench import host_spans


def read(run):
    s = host_spans.for_run(run)
    if s is None:
        return None
    n, sec = s.totals.get("sched.plan", (0, 0.0))
    return sec / n * 1e3 if n else None
