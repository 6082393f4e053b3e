"""The work the algorithm needs, counted from shapes, and the chip's peaks.

These counts are what a share of the roofline or of the peak divides by
time.  They follow the model, not the implementation: no padding to
buckets, pages or tiles, no lane that is masked off, and no output of
the head that nobody reads.  A change that removes padding therefore
leaves them alone.
"""
from __future__ import annotations

import json
from pathlib import Path

from weights import Shapes

PEAKS = Path(__file__).resolve().parent / "peaks.json"
KV_BYTES = 2  # the arena is bfloat16


def peaks(device_kind: str) -> dict:
    """The published peaks of one chip of this kind; unknown kinds fail."""
    table = json.loads(PEAKS.read_text())
    if device_kind not in table:
        raise KeyError(f"device kind {device_kind!r} is not in {PEAKS.name}; "
                       f"known: {sorted(table)}")
    return table[device_kind]


def layer_matmul_params(s: Shapes) -> int:
    """Weights one token multiplies through in one layer: q, k, v, o and
    the three SwiGLU matrices."""
    return 2 * s.d * s.q_width + 2 * s.d * s.kv_width + 3 * s.d * s.ff


def head_flops(s: Shapes) -> int:
    return 2 * s.d * s.vocab


def attn_flops_per_key(s: Shapes) -> int:
    """q.k and p.v for one query against one key, over every layer."""
    return 4 * s.heads * s.head_dim * s.layers


def kv_bytes_per_key(s: Shapes) -> int:
    """K and V of one cached token, over every layer."""
    return 2 * s.kv_width * KV_BYTES * s.layers


def token_flops(s: Shapes, keys: int, head: bool) -> int:
    """One token's forward pass, attending to `keys` cached tokens (itself
    included); `head` when its logits are read."""
    return (2 * s.layers * layer_matmul_params(s) + attn_flops_per_key(s) * keys
            + (head_flops(s) if head else 0))


def prefill_flops(s: Shapes, n: int) -> int:
    """A causal prefill of n tokens whose last logits are read."""
    return (2 * s.layers * layer_matmul_params(s) * n
            + attn_flops_per_key(s) * n * (n + 1) // 2 + head_flops(s))


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
