"""Pure-jnp oracles for every Pallas kernel in this package.

Each Pallas kernel in kernels/ must agree with the function of the same name
here — exactly (integer kernels) or to tight tolerance (float kernels).  The
integer oracles are the I-BERT algorithms from repro.core.ibert_ops; the
matmul oracle is the INT8xINT8->INT32 contract from repro.core.quant.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import ibert_ops as _io
from repro.core.quant import requantize as _requantize


def int8_matmul(a: jax.Array, b: jax.Array, s_a: jax.Array, s_b: jax.Array,
                bias: Optional[jax.Array] = None,
                s_out: Optional[jax.Array] = None) -> jax.Array:
    """INT8 (M,K) x INT8 (K,N) -> INT32 accum (+ int32 bias at scale s_a*s_b),
    optionally requantized to INT8 at s_out.  The paper's Linear module
    (Matrix-Multiply + Bias Addition + Quant, Fig. 10 layers 0/4/5)."""
    acc = jax.lax.dot_general(
        a, b, (((1,), (0,)), ((), ())), preferred_element_type=jnp.int32
    )
    if bias is not None:
        acc = acc + bias[None, :]
    if s_out is None:
        return acc
    return _requantize(acc, s_a * s_b, s_out)


def i_softmax_rows(q: jax.Array, scale: jax.Array) -> jax.Array:
    """Integer softmax over the last axis; returns int32 probs at 2^-14."""
    out, _ = _io.i_softmax(q.astype(jnp.int32), scale, axis=-1)
    return out


def i_layernorm_rows(q8: jax.Array, q_gamma: jax.Array, q_beta: jax.Array,
                     s_gamma: jax.Array) -> jax.Array:
    """Integer LayerNorm over the last axis (input int8-range int32)."""
    prep = _io.LNParams(q_gamma, s_gamma, q_beta,
                        jnp.float32(2.0 ** (-_io.LN_NORM_SHIFT)) * s_gamma)
    out, _ = _io.i_layernorm(q8.astype(jnp.int32), prep)
    return out


def i_gelu_elem(q: jax.Array, scale: jax.Array) -> jax.Array:
    out, _ = _io.i_gelu(q.astype(jnp.int32), scale)
    return out


def flash_decode(q: jax.Array, k: jax.Array, v: jax.Array,
                 kpos: jax.Array, qpos: jax.Array,
                 active: Optional[jax.Array] = None,
                 window: int = 0) -> jax.Array:
    """Single-query (decode) attention oracle for the split-KV kernel.

    q: (B, H, hd) pre-scaled by 1/sqrt(hd); k/v: (B, Sk, KVH, hd) with
    H = G * KVH (GQA — KV is never expanded); kpos: (B, Sk) int32 absolute
    key positions where 2^30 marks never-written cache slots; qpos: (B,)
    int32 absolute query position; active: optional (B,) bool row gate
    (inactive rows return exact zeros); window: sliding-window width
    (0 = unwindowed).  Masking is causal: ``kpos <= qpos`` — the sentinel
    can never pass, so fresh cache slots are unreachable by construction.
    """
    b, h, hd = q.shape
    kvh = k.shape[2]
    q5 = q.reshape(b, kvh, h // kvh, hd)
    s = jnp.einsum("bngd,bknd->bngk", q5, k).astype(jnp.float32)
    msk = kpos[:, None] <= qpos[:, None, None]  # (B, 1, Sk)
    if window:
        msk &= qpos[:, None, None] - kpos[:, None] < window
    if active is not None:
        msk &= active[:, None, None]
    msk = msk[:, :, None, :]  # (B, 1, 1, Sk) vs scores (B, KVH, G, Sk)
    s = jnp.where(msk, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(jnp.any(msk, -1, keepdims=True), p, 0.0)
    out = jnp.einsum("bngk,bknd->bngd", p.astype(q.dtype), v)
    return out.reshape(b, h, v.shape[-1])


def paged_flash_decode(q: jax.Array, k: jax.Array, v: jax.Array,
                       kpos: jax.Array, page_table: jax.Array,
                       qpos: jax.Array,
                       active: Optional[jax.Array] = None) -> jax.Array:
    """Oracle for the paged split-KV decode kernel: gather, then decode.

    q: (B, H, hd) pre-scaled; k/v: (P, ps, KVH, hd) global page arenas;
    kpos: (P, ps) absolute positions (2^30 = never written); page_table:
    (B, MAXP) int32 (entries may repeat across lanes — shared prefix
    pages).  The gathered per-lane cache is laid out exactly like the
    dense slot cache (logical position p at row p), so on equal logical
    lengths this oracle is *bitwise* identical to `flash_decode` over the
    equivalent dense cache — the property the engine equality tests lean
    on.
    """
    b = q.shape[0]
    kvh, hd = k.shape[2], k.shape[3]
    kg = k[page_table].reshape(b, -1, kvh, hd)
    vg = v[page_table].reshape(b, -1, kvh, v.shape[3])
    kpg = kpos[page_table].reshape(b, -1)
    return flash_decode(q, kg, vg, kpg, qpos, active=active)


def paged_flash_decode_latent(q: jax.Array, c: jax.Array, kpos: jax.Array,
                              page_table: jax.Array, qpos: jax.Array,
                              dv: int,
                              active: Optional[jax.Array] = None
                              ) -> jax.Array:
    """Oracle for the paged decode kernel's latent mode: keys are the
    (P, ps, R) arena's rows, values their first `dv` columns."""
    c = c[:, :, None, :]  # one kv head
    return paged_flash_decode(q, c, c[..., :dv], kpos, page_table, qpos,
                              active=active)


def expert_gmm(lhs: jax.Array, rhs: jax.Array, group_sizes: jax.Array,
               offset) -> jax.Array:
    """Oracle for the grouped expert matmul: row r of the expert-sorted
    lhs times its expert's rhs when that expert is one of the G held
    from `offset`, else 0.  f32 products, lhs.dtype result."""
    ends = jnp.cumsum(group_sizes.astype(jnp.int32))
    starts = ends - group_sizes
    row = jnp.arange(lhs.shape[0])[:, None]
    out = jnp.zeros((lhs.shape[0], rhs.shape[2]), jnp.float32)
    for g in range(rhs.shape[0]):
        e = offset + g
        mine = (row >= starts[e]) & (row < ends[e])
        out += jnp.where(mine, jnp.dot(lhs, rhs[g],
                                       preferred_element_type=jnp.float32),
                         0.0)
    return out.astype(lhs.dtype)


def paged_flash_decode_q(q: jax.Array, k: jax.Array, v: jax.Array,
                         k_scale: jax.Array, v_scale: jax.Array,
                         kpos: jax.Array, page_table: jax.Array,
                         qpos: jax.Array,
                         active: Optional[jax.Array] = None) -> jax.Array:
    """Oracle for the *quantized* paged decode kernel: gather int8 pages
    and their scales through the table, dequantize to f32
    (core/quant.kv_dequantize — bitwise the kernel's in-VMEM dequant),
    then run the dense decode oracle.

    k/v: (P, ps, KVH, hd) int8 arenas; k_scale/v_scale: (P, ps, KVH) f32
    per-row per-kv-head scales (shared prefix pages share scales by
    construction — they live in the arena, not per lane).  The dequantized
    values stay f32 through the dots, matching the kernel body, so the two
    impls agree to float tolerance and lanes sharing pages see identical
    keys.
    """
    from repro.core.quant import kv_dequantize

    b = q.shape[0]
    kvh, hd = k.shape[2], k.shape[3]
    kg = kv_dequantize(k[page_table], k_scale[page_table]).reshape(
        b, -1, kvh, hd)
    vg = kv_dequantize(v[page_table], v_scale[page_table]).reshape(
        b, -1, kvh, hd)
    kpg = kpos[page_table].reshape(b, -1)
    # q joins the dequantized values in f32 so the PV dot runs f32 like the
    # kernel body (ops.py casts the result back to q.dtype)
    return flash_decode(q.astype(jnp.float32), kg, vg, kpg, qpos,
                        active=active)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    causal: bool = True,
                    segment_ids: Optional[jax.Array] = None) -> jax.Array:
    """Float attention oracle for the blocked-attention kernel.

    q,k,v: (S, H) per head slice (already scaled by 1/sqrt(d)).  segment_ids
    implement the paper's no-padding packed sequences (§7.1): tokens attend
    only within their own segment.
    """
    s = jnp.einsum("qh,kh->qk", q, k).astype(jnp.float32)
    sq, sk = q.shape[0], k.shape[0]
    mask = jnp.ones((sq, sk), dtype=bool)
    if causal:
        mask &= jnp.tril(jnp.ones((sq, sk), dtype=bool), k=sk - sq)
    if segment_ids is not None:
        qseg, kseg = segment_ids
        mask &= qseg[:, None] == kseg[None, :]
    s = jnp.where(mask, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(jnp.any(mask, -1, keepdims=True), p, 0.0)
    return jnp.einsum("qk,kh->qh", p, v.astype(jnp.float32)).astype(q.dtype)
