"""Kernels: the paged decode kernel's share of its roofline, %.

The work is what attention needs: K and V of every key each active
lane's query attends to, at each decode step of the traced dispatches
(forced-ingest steps included), counted from the requests' real
positions; its time is the kernel's device time in the same trace."""
import costs
import devtrace as TR

KERNEL = r"^paged_flash_decode"  # the Pallas kernel's ops in the trace


def read(run):
    tr = run.trace
    if tr is None or run.peak is None or not tr["keys"]:
        return None
    secs = TR.op_seconds(tr, KERNEL)
    if secs <= 0:
        return None
    s, f, keys = run.shapes, run.family, tr["keys"]
    return costs.roofline_share(keys * f.attn_flops_per_key(s),
                                keys * f.kv_bytes_per_key(s), secs,
                                run.peak)
