"""Scheduler: host<->device round trips a step (``*.sync`` spans over
``sched.finish`` spans) in the traced stretch."""
from bench import host_spans


def read(run):
    s = host_spans.for_run(run)
    if s is None:
        return None
    return s.syncs()[0] / s.steps
