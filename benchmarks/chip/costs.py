"""The chip's peaks, and the work-count arithmetic every family shares.

A share of the roofline or of the peak divides the work the algorithm
needs by time.  Each family (families/<model_type>.py) counts its own
work from shapes (`token_flops`, `prefill_flops`, `head_flops`,
`attn_flops_per_key`, `kv_bytes_per_key`).  The counts follow the model,
not the implementation: no padding to buckets, pages or tiles, no lane
that is masked off, and no output of the head that nobody reads.  A
change that removes padding therefore leaves them alone.
"""
from __future__ import annotations

import json
from pathlib import Path

PEAKS = Path(__file__).resolve().parent / "peaks.json"
KV_BYTES = 2  # the arena is bfloat16


def peaks(device_kind: str) -> dict:
    """The published peaks of one chip of this kind; unknown kinds fail."""
    table = json.loads(PEAKS.read_text())
    if device_kind not in table:
        raise KeyError(f"device kind {device_kind!r} is not in {PEAKS.name}; "
                       f"known: {sorted(table)}")
    return table[device_kind]


def decode_keys(p0: int, steps: int) -> int:
    """Keys read by `steps` single-token steps starting at position p0
    (the step at position q attends to q + 1 keys)."""
    return steps * (p0 + 1) + steps * (steps - 1) // 2


def roofline_share(flops: float, nbytes: float, seconds: float,
                   peak: dict) -> float:
    """The least time the chip could take for this work over the time it
    took, in %."""
    least = max(flops / peak["bf16_flops_per_s"],
                nbytes / peak["hbm_bytes_per_s"])
    return 100.0 * least / seconds
