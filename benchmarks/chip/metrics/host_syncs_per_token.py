"""Executor: blocking device-to-host syncs per output token."""


def read(run):
    out = sum(len(r.tokens_out) for r in run.requests)
    syncs = run.stats.get("device_syncs")
    if not out or syncs is None:
        return None
    return syncs / out
