"""KV pool: peak share of the pool's blocks in use over the window's steps
(``BlockPool`` block occupancy after each step), in %."""


def read(run):
    if not run.steps:
        return None
    return 100.0 * max(s.block_use for s in run.steps)
