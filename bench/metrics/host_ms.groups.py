"""Group profiles: host time a step in the CFG group loop
(``Scheduler._commit_groups``, its ``sched.groups`` span: each group's row
gather, profile step and sync), over the traced stretch, in ms."""
from bench import host_spans


def read(run):
    s = host_spans.for_run(run)
    if s is None:
        return None
    return s.seconds("sched.groups") / s.steps * 1e3
