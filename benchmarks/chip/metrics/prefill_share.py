"""Executor: cold admissions (prefill through the first token's sync) over
the serving loop's host wall time, whole run, %."""


def read(run):
    loop, pre = run.stats.get("loop_s"), run.stats.get("prefill_s")
    if not loop or pre is None:
        return None
    return 100.0 * pre / loop
