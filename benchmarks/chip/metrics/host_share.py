"""Scheduler: the serving loop's host wall time neither blocked on a
device-to-host read nor waiting for arrivals, over the loop's, whole
run, %."""


def read(run):
    s = run.stats
    loop = s.get("loop_s")
    if not loop or s.get("sync_s") is None or s.get("idle_s") is None:
        return None
    return 100.0 * (loop - s["sync_s"] - s["idle_s"]) / loop
