"""90th percentile, over every request with two or more tokens inside the
window, of (last token in the window - first token) / (tokens - 1)
(host clock)."""
import numpy as np


def read(run):
    v = []
    for r in run.requests:
        ts = [t for t in r.t_tokens if 0 <= t <= run.seconds]
        if len(ts) >= 2:
            v.append((ts[-1] - ts[0]) / (len(ts) - 1))
    return float(np.percentile(v, 90)) * 1e3 if v else None
