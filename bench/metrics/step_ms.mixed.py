"""Step executables: device time per packed mixed step (prompt chunks beside
decode rows), from the trace's program events (``mixed_step`` and its
tensor-parallel twin), device 0."""


def read(run):
    if run.trace is None:
        return None
    s, n = run.trace.module_s("mixed_step")
    return s / n * 1e3 if n else None
