"""Step executables: device time per paged decode step, from the trace's
program events (``decode_step`` and its tensor-parallel twin), device 0."""


def read(run):
    if run.trace is None:
        return None
    s, n = run.trace.module_s("decode_step")
    return s / n * 1e3 if n else None
