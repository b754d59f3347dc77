"""Host<->device sync: host time a step inside the program's round trips
(every ``*.sync`` span: the step's ``device_get`` and each group's), over
the traced stretch, in ms."""
from bench import host_spans


def read(run):
    s = host_spans.for_run(run)
    if s is None:
        return None
    return s.syncs()[1] / s.steps * 1e3
