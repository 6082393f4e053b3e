"""The plain reference's shared parts, and the comparison of served tokens.

Each family (families/<model_type>.py) writes its own float32 forward
pass from the published architecture, out of the pieces here.  The
reference imports nothing of the program under test.  Its weights come
from the seed, one layer at a time (the served model holds the same
bfloat16 values), and are upcast to float32; every product runs at
HIGHEST precision.  One layer at a time over every sampled sequence
keeps the largest configuration inside one chip.

The control runs the same pass with every projection in W8A8, as the
program's own int8 serving path does it, and is read at the positions
the program served: int8 weights per output channel, int8 activations
with one scale per tensor (here, over the sequence's real tokens),
integer products summed in int32, the head left in float.  That is the
lower precision a later change would be tempted by, and the comparison
has to fail it.  A second control rounds the same weights (per output
channel) and activations (per tensor) to float8 e4m3, the other step
below bfloat16, and multiplies in float32.
"""
from __future__ import annotations

import functools
from typing import List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
PAD = 512  # sequences are padded to a multiple of this (causal: harmless)


def _mm(x, w):
    return jnp.matmul(x, w, precision=HI)


def _quant_w(w):
    """(in, out) float32 -> int8 per output channel, f32 scales (1, out)."""
    s = jnp.maximum(jnp.max(jnp.abs(w), axis=0, keepdims=True), 1e-12) / 127.0
    return jnp.clip(jnp.round(w / s), -127, 127).astype(jnp.int8), s


F8 = jnp.float8_e4m3fn
F8_MAX = 448.0


def _f8(x, keep):
    """x rounded to float8 e4m3 under a scale that maps the largest |x|
    (over the rows `keep` selects) to its largest value."""
    s = jnp.maximum(jnp.max(jnp.where(keep, jnp.abs(x), 0.0), axis=0,
                            keepdims=True), 1e-12) / F8_MAX
    return (x / s).astype(F8).astype(jnp.float32) * s


def _mm_f8(x, w, valid):
    rows = (jnp.arange(x.shape[0]) < valid)[:, None]
    xs = _f8(x, rows)
    return _mm(jnp.where(rows, xs, 0.0), w)


def _mm_q(x, w, valid):
    q, sw = w
    rows = (jnp.arange(x.shape[0]) < valid)[:, None]
    sx = jnp.maximum(jnp.max(jnp.where(rows, jnp.abs(x), 0.0)), 1e-12) / 127.0
    xq = jnp.clip(jnp.round(x / sx), -127, 127).astype(jnp.int8)
    acc = jax.lax.dot_general(xq, q, (((1,), (0,)), ((), ())),
                              preferred_element_type=jnp.int32)
    return acc.astype(jnp.float32) * sx * sw


def projection(quant: str, valid):
    """The product of a projection: float32 at HIGHEST, or a control's
    (`quant` "w8a8" or "fp8") over the sequence's `valid` real rows."""
    return {"": _mm, "w8a8": functools.partial(_mm_q, valid=valid),
            "fp8": functools.partial(_mm_f8, valid=valid)}[quant]


def upcast(w: dict, quant: str) -> dict:
    """A layer's bfloat16 matrices in float32, quantised as `quant` says."""
    w = {k: v.astype(jnp.float32) for k, v in w.items()}
    if quant == "w8a8":
        w = {k: _quant_w(v) for k, v in w.items()}
    elif quant == "fp8":
        w = {k: _f8(v, True) for k, v in w.items()}
    return w


def rms(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def rope(x, theta):
    """x: (S, heads, hd); rotate-half RoPE at positions 0..S-1."""
    s, _, hd = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def embed(tok, seqs: Sequence[np.ndarray]) -> list:
    """Each sequence's float32 embeddings, padded to a multiple of PAD."""
    xs = []
    for seq in seqs:
        n = -(-len(seq) // PAD) * PAD
        ids = np.zeros((n,), np.int32)
        ids[:len(seq)] = seq
        xs.append(tok[jnp.asarray(ids)].astype(jnp.float32))
    return xs


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, idx, emb, eps: float):
    h = rms(x[idx], eps)
    head = emb["head"] if "head" in emb else emb["tok"].T
    return _mm(h, head.astype(jnp.float32))


def head_logits(xs, positions, emb, eps: float) -> List[np.ndarray]:
    """Final RMSNorm and the head (untied, or the token table's
    transpose) at the given positions of each sequence."""
    return [np.asarray(_head(x, jnp.asarray(np.asarray(p, np.int32)), emb,
                             eps))
            for x, p in zip(xs, positions)]


def served_gaps(family, s, seed: int,
                served: Sequence[Tuple[np.ndarray, np.ndarray]],
                controls: Sequence[str] = ()):
    """For each (prompt, served tokens): the reference's gap of each served
    token (its best logit minus the served token's); and for each control
    named (from the family's CONTROLS), the gap of the token it puts first
    at the same positions, as if it had served them.  Returns a list of
    float arrays and a dict of such lists by control."""
    seqs, pos, toks = [], [], []
    for prompt, out in served:
        out = np.asarray(out, np.int32)
        seqs.append(np.concatenate([np.asarray(prompt, np.int32), out[:-1]]))
        pos.append(np.arange(len(seqs[-1]) - len(out), len(seqs[-1])))
        toks.append(out)
    ref = family.logits(s, seed, seqs, pos)
    gaps = [r.max(-1) - r[np.arange(len(t)), t] for r, t in zip(ref, toks)]
    ctl = {}
    for q in controls:
        c = family.logits(s, seed, seqs, pos, quant=q)
        ctl[q] = [r.max(-1) - r[np.arange(len(r)), x.argmax(-1)]
                  for r, x in zip(ref, c)]
    return gaps, ctl
