"""Tokens delivered to requests inside the window, over the window (host
clock). A contrastive pair delivers one token a step."""


def read(run):
    n = sum(1 for r in run.requests for t in r.t_tokens if 0 <= t <= run.seconds)
    return n / run.seconds
