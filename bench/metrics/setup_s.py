"""Process start to the first timed step (host clock): weights, pool, warm-up
and, in a run that compiles, compilation."""


def read(run):
    return run.setup_s
