"""KV manager: prompt tokens served from the radix prefix cache over the
prompt tokens submitted, in %."""


def read(run):
    total = sum(len(r.prompt) for r in run.requests)
    hit = run.stats.get("prefix_hit_tokens")
    if not total or hit is None:
        return None
    return 100.0 * hit / total
