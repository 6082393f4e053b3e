"""Output tokens handed to requests inside the window, over the window."""


def read(run):
    t1 = run.t0 + run.seconds
    n = sum(run.t0 <= t < t1 for r in run.requests for t in r.t_tokens)
    return n / run.seconds
