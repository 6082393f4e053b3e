"""Kernels: the grouped expert matmul's share of its roofline in the
decode program, %.

Its time is the device time of the grouped-matmul calls in the trace
(megablox's `gmm` Pallas kernel, which the program calls through
`ops.expert_gmm`; the trace names them `gmm.<n>`) whose rows are the
decode batch's (max_batch * top_k, padded to the kernel's row tile; a
prefill's calls hold a bucket's rows), plus the ops that stage their
weights: the layer scan slices each layer's held experts out of the
stacked parameters into on-chip memory (ops of shape [held, d, moe_ff]
and [held, moe_ff, d]), and the kernel reads them there.  That staging
is where the experts' bytes leave HBM; without it the kernel's own time
reads above the peak's.  Prefills stage weights of the same shapes, so
their copies inside the trace count too, and the share reads low by
their part.  Its work is that of the traced decode steps.
Which dispatches the trace holds is rebuilt from the requests' token
blocks: the run of consecutive dispatches whose keys (counted as the
harness counts them, from each lane's position and tokens) add up to
the trace's own key count.  Over those steps:

- bytes: each hit expert's three matrices once a layer a step, at the
  whole run's average of held experts hit a step (engine counter
  `moe_experts_hit` over `decode_steps`), plus each held pair's rows
  (in twice, the two hidden halves out, the hidden in, the row out);
- FLOPs: 2*3*d*moe_ff a held pair, at the whole run's share of a lane's
  pairs that land here (`moe_pairs_held` over `moe_pairs_routed`).

No reading where the program has no expert share, or the trace no call
or no staging of its weights.
"""
from itertools import groupby

import costs
import devtrace as TR


def _rup(x: int, m: int) -> int:
    return -(-x // m) * m


def _dispatches(run):
    """{stamp: [steps, keys, lane steps]} of every decode dispatch, from
    the requests' blocks after each one's first token."""
    out = {}
    for r in run.requests:
        blocks = [(b, len(list(g))) for b, g in groupby(r.blocks)]
        seen = blocks[0][1] if blocks else 0
        for b, n in blocks[1:]:
            d = out.setdefault(b, [0, 0, 0])
            d[0] = max(d[0], n)
            d[1] += costs.decode_keys(len(r.prompt) + seen - 1, n)
            d[2] += n
            seen += n
    return out


def _traced(run, keys: int):
    """(steps, lane steps) of the consecutive dispatches whose keys add up
    to the trace's, the run nearest 40% into the window; None if none."""
    ds = sorted(_dispatches(run).items())
    acc = [0]
    for _, (_, k, _) in ds:
        acc.append(acc[-1] + k)
    at = run.t0 + 0.4 * run.seconds
    best = None
    for i in range(len(ds)):
        for j in range(i + 1, len(ds) + 1):
            if acc[j] - acc[i] > keys:
                break
            if acc[j] - acc[i] == keys:
                off = abs(ds[i][0] - at)
                if best is None or off < best[0]:
                    best = (off, i, j)
    if best is None:
        return None
    part = [v for _, v in ds[best[1]:best[2]]]
    return sum(v[0] for v in part), sum(v[2] for v in part)


def read(run):
    tr, st, f, s = run.trace, run.stats, run.family, run.shapes
    if tr is None or run.peak is None or not tr["keys"] \
            or not hasattr(f, "expert_bytes") \
            or not st.get("moe_pairs_routed") or not st.get("decode_steps"):
        return None
    rows = run.max_batch * s.top_k
    rows = _rup(rows, min(128, _rup(rows, 16)))
    secs = TR.op_seconds(tr, rf"^gmm[\w.\-]* \w+\[{rows},")
    staged = sum(TR.op_seconds(tr, rf"^\S+ \w+\[{s.held},{a},{b}\] ")
                 for a, b in ((s.d, s.moe_ff), (s.moe_ff, s.d)))
    got = _traced(run, tr["keys"])
    if secs <= 0 or staged <= 0 or got is None or not got[0]:
        return None
    secs += staged
    steps, lane_steps = got
    pairs = (lane_steps * s.top_k * st["moe_pairs_held"]
             / st["moe_pairs_routed"]) * (s.layers - s.dense)
    hit = st["moe_experts_hit"] / st["decode_steps"]
    nbytes = (steps * hit * f.expert_bytes(s)
              + pairs * 2 * 3 * (s.d + s.moe_ff))
    return costs.roofline_share(pairs * f.expert_pair_flops(s), nbytes,
                                secs, run.peak)
