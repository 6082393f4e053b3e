"""Scheduler: 95th percentile of admission minus scheduled arrival, ms."""
import numpy as np


def read(run):
    v = [(r.t_admitted - r.t_enqueue) * 1e3 for r in run.requests
         if r.tokens_out]
    return float(np.percentile(v, 95)) if v else None
