"""Executor: host wall time from a decode dispatch to the return of its
block sync, per decode step, whole run, ms."""


def read(run):
    dec, steps = run.stats.get("decode_s"), run.stats.get("decode_steps")
    if not steps or dec is None:
        return None
    return 1e3 * dec / steps
