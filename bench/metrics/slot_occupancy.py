"""Scheduler: mean share of the pool's slots in use over the window's
steps (the scheduler's per-step occupancy counter), in %."""


def read(run):
    if not run.steps:
        return None
    return 100.0 * sum(s.occupancy for s in run.steps) / len(run.steps)
