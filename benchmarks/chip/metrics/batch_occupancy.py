"""Scheduler: occupied lane-steps over decode steps times max_batch, %."""


def read(run):
    steps = run.stats.get("decode_steps")
    lanes = run.stats.get("active_lane_steps")
    if not steps or lanes is None:
        return None
    return 100.0 * lanes / (steps * run.max_batch)
