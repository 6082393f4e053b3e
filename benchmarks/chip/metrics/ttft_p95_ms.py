"""95th percentile over all requests of the first token's hand-off minus
the scheduled arrival, in ms.  A request that never got its first token
counts as later than every other: where the percentile reaches one, it is
infinite (and the run is not correct, since a request failed)."""
import math

import numpy as np


def read(run):
    if not run.requests:
        return None
    got = [(r.t_tokens[0] - r.t_enqueue) * 1e3 for r in run.requests
           if r.t_tokens]
    n = len(run.requests)
    if math.ceil(0.95 * (n - 1)) >= len(got):
        return math.inf
    # the missing sort above every rank the percentile reads
    return float(np.percentile(got + [max(got)] * (n - len(got)), 95))
