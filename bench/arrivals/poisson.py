"""Open loop at ``rate_per_s``.  The count is fixed at round(rate x
seconds) and the arrival times are sorted uniform draws over the window
(a Poisson process given its count), so every seed sends the same amount
of work, in another order and at other times."""
import numpy as np

from harness import traffic as T

LOOP = "open"


def schedule(params, config, seconds, seed):
    total = max(1, int(round(float(params["rate_per_s"]) * seconds)))
    r = T.rng(seed, 1)
    sizes = T.request_sizes(config, total, r)
    due = np.sort(r.uniform(0.0, seconds, total))
    return due.tolist(), sizes
