"""95th percentile, over every output token of every request after its
first block, of the time per output token, in ms.

Tokens reach the host in blocks, one block per decode dispatch of n
steps.  A block's time runs from the hand-off of the request's previous
block to its own, and each of its tokens takes that time over n, the
most tokens any request got in that block: the decode loop's cadence as
the request sees it, with every stall between dispatches (admissions,
prefills, host syncs) spread over the steps after it.  A request's last
block can hold fewer than n tokens; it is not taken over its own count,
which would make the tail follow how budgets fall against horizons."""
from collections import Counter

import numpy as np


def read(run):
    steps = Counter()
    for r in run.requests:
        for b, k in Counter(r.blocks).items():
            steps[b] = max(steps[b], k)
    v = []
    for r in run.requests:
        for i in range(1, len(r.blocks)):
            b = r.blocks[i]
            if b == r.blocks[i - 1]:
                continue
            n = r.blocks.count(b)
            v += [(r.t_tokens[i] - r.t_tokens[i - 1]) * 1e3 / steps[b]] * n
    return float(np.percentile(v, 95)) if v else None
