"""Attention: GQA/MQA, RoPE, sliding window, KV-chunked online softmax, decode.

Long-prefill shapes (32k) cannot materialize (S,S) score matrices even
sharded; `_chunked_attention` streams KV blocks through an online-softmax
scan (flash-attention recurrence expressed in jax.lax so XLA/SPMD can shard
it; the Pallas-fused variant is a §Perf item).  Decode attends one query row
against the full cache.  Segment ids implement the paper's no-padding packed
sequences (§7.1).
"""
from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models.layers import COMPUTE_DTYPE, apply_rope, dense_init

Params = Dict[str, Any]

DENSE_ATTN_MAX_KV = 1024  # above this, use the KV-chunked online-softmax path
# bigger KV chunks = proportionally fewer (m,l,acc) carry round-trips
# through HBM in the online-softmax scan (§Perf C2a: the scan-carry traffic
# dominated the 32k-prefill memory term); 1024 balances that against the
# checkpointed-backward recompute peak, which grows with chunk size
KV_CHUNK = 1024

NEG_INF = -1e30


def attn_init(key, cfg) -> Params:
    if cfg.kv_lora_rank:
        return mla_init(key, cfg)
    d, nh, nkv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 4)
    return {
        "wq": dense_init(ks[0], d, nh * hd),
        "wk": dense_init(ks[1], d, nkv * hd),
        "wv": dense_init(ks[2], d, nkv * hd),
        "wo": dense_init(ks[3], nh * hd, d),
    }


def _split_heads(x, n):  # (B,S,n*hd) -> (B,S,n,hd)
    b, s, _ = x.shape
    return x.reshape(b, s, n, -1)


def _repeat_kv(k, q_per_kv):  # (B,S,nkv,hd) -> (B,S,nh,hd)
    # retained for reference; the attention paths below use GROUPED einsums
    # instead — materializing the q_per_kv-expanded KV cache cost up to
    # 7x cache bytes (deepseek decode: 134 GB/chip, §Perf 0.7)
    if q_per_kv == 1:
        return k
    return jnp.repeat(k, q_per_kv, axis=2)


def _mask(sq: int, sk: int, q_pos, k_pos, causal: bool, window: int,
          q_seg=None, k_seg=None):
    """(B, sq, sk) bool mask from absolute positions."""
    m = jnp.ones((q_pos.shape[0], sq, sk), dtype=bool)
    if causal:
        m &= q_pos[:, :, None] >= k_pos[:, None, :]
    if window:
        m &= q_pos[:, :, None] - k_pos[:, None, :] < window
    if q_seg is not None:
        m &= q_seg[:, :, None] == k_seg[:, None, :]
    return m


def _gq_scores(q, k) -> jax.Array:
    """Grouped scores without expanding KV: q:(B,Sq,H,hd) k:(B,Sk,KVH,hd)
    -> (B,H,Sq,Sk).  Materializing repeat_kv cost up to 7x cache bytes
    (deepseek decode: 134 GB/chip, §Perf 0.7)."""
    b, sq, h, hd = q.shape
    kvh = k.shape[2]
    q5 = q.reshape(b, sq, kvh, h // kvh, hd)
    s = jnp.einsum("bqngd,bknd->bngqk", q5, k)
    return s.reshape(b, h, sq, k.shape[1])


def _gq_pv(p, v) -> jax.Array:
    """p:(B,H,Sq,Sk) x v:(B,Sk,KVH,hd) -> (B,Sq,H,hd), grouped."""
    b, h, sq, sk = p.shape
    kvh, hd = v.shape[2], v.shape[3]
    p5 = p.reshape(b, kvh, h // kvh, sq, sk)
    out = jnp.einsum("bngqk,bknd->bqngd", p5, v)
    return out.reshape(b, sq, h, hd)


def _dense_attention(q, k, v, mask) -> jax.Array:
    """q:(B,Sq,H,hd) k/v:(B,Sk,KVH,hd) mask:(B,Sq,Sk)."""
    s = _gq_scores(q, k).astype(jnp.float32)
    s = jnp.where(mask[:, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(jnp.any(mask[:, None], -1, keepdims=True), p, 0.0)
    return _gq_pv(p.astype(q.dtype), v)


def _chunked_attention(q, k, v, q_pos, k_pos, causal, window,
                       q_seg=None, k_seg=None) -> jax.Array:
    """Online-softmax over KV chunks; O(Sq * KV_CHUNK) live scores."""
    b, sq, h, hd = q.shape
    sk, kvh = k.shape[1], k.shape[2]
    chunk = min(KV_CHUNK, sk)
    pad = (-sk) % chunk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        k_pos = jnp.pad(k_pos, ((0, 0), (0, pad)), constant_values=2**30)
        if k_seg is not None:
            k_seg = jnp.pad(k_seg, ((0, 0), (0, pad)), constant_values=-2)
    n_chunks = k.shape[1] // chunk

    kc = k.reshape(b, n_chunks, chunk, kvh, hd).swapaxes(0, 1)
    vc = v.reshape(b, n_chunks, chunk, kvh, v.shape[-1]).swapaxes(0, 1)
    pc = k_pos.reshape(b, n_chunks, chunk).swapaxes(0, 1)
    sc = (k_seg.reshape(b, n_chunks, chunk).swapaxes(0, 1)
          if k_seg is not None else None)

    def body(carry, xs):
        m_run, l_run, acc = carry
        if sc is not None:
            kx, vx, px, sx = xs
        else:
            kx, vx, px = xs
            sx = None
        s = _gq_scores(q, kx).astype(jnp.float32)
        msk = _mask(sq, chunk, q_pos, px, causal, window, q_seg, sx)
        s = jnp.where(msk[:, None], s, NEG_INF)
        m_new = jnp.maximum(m_run, jnp.max(s, axis=-1))
        alpha = jnp.exp(m_run - m_new)
        p = jnp.exp(s - m_new[..., None])
        l_new = l_run * alpha + p.sum(-1)
        # NB: a bf16 accumulator carry was tried (§Perf C2a-refuted): it
        # halves carry bytes but compounds rescaling error over 16+ chunks
        # and flipped greedy tokens in the serving tests — f32 it stays.
        acc = acc * alpha[..., None] + _gq_pv(
            p.astype(q.dtype), vx).swapaxes(1, 2).astype(jnp.float32)
        return (m_new, l_new, acc), None

    init = (
        jnp.full((b, h, sq), NEG_INF, jnp.float32),
        jnp.zeros((b, h, sq), jnp.float32),
        jnp.zeros((b, h, sq, v.shape[-1]), jnp.float32),
    )
    xs = (kc, vc, pc) + ((sc,) if sc is not None else ())
    # checkpoint the chunk body: backward recomputes each chunk's scores
    # instead of saving (B,H,Sq,chunk) residuals per step (flash-attn-style
    # memory: carries only) — §Perf iteration 1
    (m_run, l_run, acc), _ = jax.lax.scan(jax.checkpoint(body), init, xs)
    out = acc / jnp.maximum(l_run, 1e-30)[..., None]
    return out.swapaxes(1, 2).astype(q.dtype)  # (B,Sq,H,hd)


def _windowed_attention(q, k, v, q_pos, k_pos, window,
                        q_seg=None, k_seg=None) -> jax.Array:
    """Causal sliding-window attention in O(S*2W): query blocks of size W
    attend only to their own and the previous KV block (§Perf A4 — the
    full chunked path wastes 8x attention FLOPs at 32k/W=2048)."""
    b, s, h, hd = q.shape
    blk = window
    pad = (-s) % blk
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        q_pos = jnp.pad(q_pos, ((0, 0), (0, pad)), constant_values=2**30)
        k_pos = jnp.pad(k_pos, ((0, 0), (0, pad)), constant_values=2**30)
        if q_seg is not None:
            q_seg = jnp.pad(q_seg, ((0, 0), (0, pad)), constant_values=-2)
            k_seg = jnp.pad(k_seg, ((0, 0), (0, pad)), constant_values=-3)
    nb = q.shape[1] // blk

    def blocks(a):  # (B, nb, blk, ...)
        return a.reshape(b, nb, blk, *a.shape[2:])

    qb, kb, vb = blocks(q), blocks(k), blocks(v)
    qpb, kpb = blocks(q_pos), blocks(k_pos)
    # KV for block i = concat(block i-1, block i); block -1 is zeros/masked
    kprev = jnp.concatenate([jnp.zeros_like(kb[:, :1]), kb[:, :-1]], axis=1)
    vprev = jnp.concatenate([jnp.zeros_like(vb[:, :1]), vb[:, :-1]], axis=1)
    kpprev = jnp.concatenate(
        [jnp.full_like(kpb[:, :1], 2**30), kpb[:, :-1]], axis=1)
    k2 = jnp.concatenate([kprev, kb], axis=2)  # (B, nb, 2*blk, H, hd)
    v2 = jnp.concatenate([vprev, vb], axis=2)
    kp2 = jnp.concatenate([kpprev, kpb], axis=2)
    if q_seg is not None:
        qsb, ksb = blocks(q_seg), blocks(k_seg)
        ksprev = jnp.concatenate(
            [jnp.full_like(ksb[:, :1], -3), ksb[:, :-1]], axis=1)
        ks2 = jnp.concatenate([ksprev, ksb], axis=2)

    def one(qc, kc, vc, qp, kp, qs=None, ks=None):
        msk = _mask(blk, 2 * blk, qp, kp, True, window, qs, ks)
        return _dense_attention(qc, kc, vc, msk)

    args = (qb, k2, v2, qpb, kp2) + ((qsb, ks2) if q_seg is not None else ())
    out = jax.vmap(one, in_axes=1, out_axes=1)(*args)
    return out.reshape(b, nb * blk, h, hd)[:, :s]


def attention(x: jax.Array, p: Params, cfg, positions: jax.Array,
              segment_ids: Optional[jax.Array] = None,
              cache: Optional[Dict[str, jax.Array]] = None,
              pos_contiguous: bool = False,
              page_table: Optional[jax.Array] = None,
              active: Optional[jax.Array] = None,
              ) -> Tuple[jax.Array, Optional[Dict[str, jax.Array]]]:
    """Full attention block.

    Training/prefill: cache=None -> self-attention over x.
    Decode: cache={'k','v','pos'} -> write x's KV at cache['pos'], attend to
    the whole (ring-buffered if local_window) cache.
    pos_contiguous: caller guarantees positions == broadcast(arange(S)) (no
    pad sentinels), so the purely positional mask of the Pallas
    flash-attention kernel is exact and long prefill may route through it.
    page_table: (B, MAXP) int32 — marks the cache as a *paged* KV arena
    (`k`/`v`: (P, ps, KVH, hd), `kpos`: (P, ps)): lane b's logical position
    q lives at arena page page_table[b, q // ps], offset q % ps.  Decode
    writes are scattered through the table; `active` gates them (inactive
    lanes write to the allocator's trash page 0 with sentinel kpos, so a
    parked lane can never corrupt live pages — the paged analogue of the
    dense path's cache_map where-masking).
    """
    if cfg.kv_lora_rank:
        return mla_attention(x, p, cfg, positions, segment_ids, cache,
                             page_table, active)
    from repro.kernels import ops as kops
    from repro.models.layers import dense
    from repro.models.shard_hints import (
        fsdp_int8_gather, hint, paged_shard_ctx,
    )

    nh, nkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    wq = fsdp_int8_gather(p["wq"], tp_dim=1)  # no-op unless enabled
    wk = fsdp_int8_gather(p["wk"], tp_dim=1)
    wv = fsdp_int8_gather(p["wv"], tp_dim=1)
    # NB: sharding k/v on head_dim to match a TP-sharded cache was tried
    # (§Perf A5-refuted): the score contraction then needs per-chunk psums,
    # 3x the collective bytes of the one-off cache-write reshard.
    q = hint(_split_heads(dense(x, wq), nh), "bshd")
    k = hint(_split_heads(dense(x, wk), nkv), "bshd")
    v = hint(_split_heads(dense(x, wv), nkv), "bshd")
    # q stays unscaled here: the fused prefill kernel applies 1/sqrt(hd)
    # itself, every other path takes the pre-scaled qs below
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    qs = q * (1.0 / math.sqrt(hd))

    window = cfg.local_window
    impl = kops.default_impl()

    if cache is not None and page_table is not None and x.shape[1] > 1:
        # paged verify: Sq = T > 1 contiguous queries against the arena —
        # the speculative-decoding target pass (docs/serving.md
        # §speculative decoding).  Each of the T rows is scattered exactly
        # like the single-query decode write below, then each query attends
        # through the same paged kernel at its own absolute position; rows
        # ahead of a query carry kpos greater than its qpos, so the causal
        # mask hides them.  Row i of the output is therefore bitwise
        # identical to what a single-step paged decode at positions[:, i]
        # would have produced — which is what makes greedy verification
        # lossless.  Rejected speculation needs no cache cleanup: rewinding
        # the position counter leaves the garbage rows at kpos greater than
        # every future query position until overwritten (causally
        # unreachable).
        assert not window, "paged KV does not support sliding windows"
        b, t = x.shape[0], x.shape[1]
        ck, cv = cache["k"], cache["v"]  # (P, ps, KVH, hd)
        ps = ck.shape[1]
        quantized = "k_scale" in cache
        act = (jnp.ones((b,), bool) if active is None
               else active.astype(bool))
        # one batched (B*T)-row scatter: within a lane the T positions are
        # distinct, across lanes only exclusively-owned write pages are
        # touched, so the only duplicate indices are inactive rows on the
        # trash page — and those all write the same sentinel kpos, so
        # scatter order can't matter
        cpos = positions.astype(jnp.int32)  # (B, T)
        page = jnp.take_along_axis(page_table, cpos // ps, axis=1)
        wr_page = jnp.where(act[:, None], page, 0).reshape(-1)
        wr_off = jnp.where(act[:, None], cpos % ps, 0).reshape(-1)
        kpos_val = jnp.where(act[:, None], cpos, jnp.int32(2 ** 30))
        kpos = cache["kpos"].at[wr_page, wr_off].set(kpos_val.reshape(-1))
        kf = k.reshape(b * t, nkv, hd)
        vf = v.reshape(b * t, nkv, hd)
        if quantized:
            from repro.core.quant import kv_quantize
            kq, ksc = kv_quantize(kf)
            vq, vsc = kv_quantize(vf)
            ck = ck.at[wr_page, wr_off].set(kq)
            cv = cv.at[wr_page, wr_off].set(vq)
            cks = cache["k_scale"].at[wr_page, wr_off].set(ksc)
            cvs = cache["v_scale"].at[wr_page, wr_off].set(vsc)
        else:
            ck = ck.at[wr_page, wr_off].set(kf.astype(ck.dtype))
            cv = cv.at[wr_page, wr_off].set(vf.astype(cv.dtype))
        route = "pallas" if (impl == "pallas" and cfg.causal) else "ref"
        ctx = paged_shard_ctx()
        mesh_kw = {} if ctx is None else {"mesh": ctx[0], "axis": ctx[1]}
        # fold the T contiguous queries into the batch axis: ONE kernel
        # dispatch for the whole block — (B*T) lanes sharing the arena,
        # each query attending at its own absolute position.  Per-row
        # attention has no cross-batch reduction, so row (b, i) is bitwise
        # what a single-step paged decode at positions[b, i] would produce
        # — at one dispatch's cost instead of T.
        qf = qs.reshape(b * t, nh, hd)
        ptf = jnp.repeat(page_table, t, axis=0)  # (B*T, MAXP)
        qpf = cpos.reshape(-1)
        actf = jnp.repeat(act, t)
        if quantized:
            of = kops.paged_flash_decode_q(qf, ck, cv, cks, cvs, kpos, ptf,
                                           qpf, active=actf, impl=route,
                                           **mesh_kw)
        else:
            of = kops.paged_flash_decode(qf, ck.astype(q.dtype),
                                         cv.astype(q.dtype), kpos, ptf, qpf,
                                         active=actf, impl=route, **mesh_kw)
        out = of.reshape(b, t, nh, hd)
        new_cache = {"k": ck, "v": cv, "kpos": kpos}
        if quantized:
            new_cache.update({"k_scale": cks, "v_scale": cvs})
    elif cache is None or x.shape[1] > 1:
        if x.shape[1] <= DENSE_ATTN_MAX_KV:
            msk = _mask(x.shape[1], x.shape[1], positions, positions,
                        cfg.causal, window, segment_ids, segment_ids)
            out = _dense_attention(qs, k, v, msk)
        elif (impl != "ref" and pos_contiguous and segment_ids is None
              and not window
              and kops.fused_grid_ok(
                  impl, x.shape[0] * nh, (-(-x.shape[1] // 256)) ** 2)):
            # long prefill on the Pallas kernel: online-softmax carries live
            # in VMEM instead of round-tripping HBM per KV chunk (§Perf C2a)
            out = kops.flash_attention(q, k, v, causal=cfg.causal, impl=impl)
        elif window and cfg.causal and x.shape[1] > 2 * window:
            out = _windowed_attention(qs, k, v, positions, positions,
                                      window, segment_ids, segment_ids)
        else:
            out = _chunked_attention(qs, k, v, positions, positions,
                                     cfg.causal, window, segment_ids,
                                     segment_ids)
        if cache is None:
            new_cache = None
        else:
            # prefill: write the (last `slots`) KV + their absolute positions
            s = x.shape[1]
            slots = cache["k"].shape[1]
            take = min(s, slots)
            kw, vw = k[:, -take:], v[:, -take:]
            pw = positions[:, -take:].astype(jnp.int32)
            idx = (jnp.arange(s - take, s, dtype=jnp.int32) % slots
                   if window else jnp.arange(take, dtype=jnp.int32))
            ck = cache["k"].at[:, idx].set(kw.astype(cache["k"].dtype))
            cv = cache["v"].at[:, idx].set(vw.astype(cache["v"].dtype))
            ckp = cache["kpos"].at[:, idx].set(pw)
            new_cache = {"k": ck, "v": cv, "kpos": ckp}
    elif page_table is not None:
        # paged decode: Sq == 1 against the global page arena.  The write
        # is a (page, offset) scatter through the lane's page table; the
        # copy-on-write alignment rule (serving engine) guarantees an
        # active lane's current write page is exclusively owned, so lanes
        # never race on a page.  Inactive lanes are redirected to the
        # trash page (0) with sentinel kpos instead of being where-masked
        # afterwards — an arena has no batch axis to mask over.
        assert not window, "paged KV does not support sliding windows"
        b = x.shape[0]
        ck, cv = cache["k"], cache["v"]  # (P, ps, KVH, hd)
        ps = ck.shape[1]
        quantized = "k_scale" in cache  # int8 arena + per-row scales
        cpos = positions[:, 0].astype(jnp.int32)
        act = (jnp.ones((b,), bool) if active is None
               else active.astype(bool))
        page = page_table[jnp.arange(b), cpos // ps]
        wr_page = jnp.where(act, page, 0)
        wr_off = jnp.where(act, cpos % ps, 0)
        kpos_val = jnp.where(act, cpos, jnp.int32(2 ** 30))
        if quantized:
            # the scatter quantizes the new row on the way in: one int8
            # row + one f32 scale per kv head, same (page, offset) address
            # as the values — inactive lanes' rows land in the trash page
            # with sentinel kpos exactly like the bf16 arena's
            from repro.core.quant import kv_quantize
            kq, ksc = kv_quantize(k[:, 0])  # (B, KVH, hd) int8, (B, KVH)
            vq, vsc = kv_quantize(v[:, 0])
            ck = ck.at[wr_page, wr_off].set(kq)
            cv = cv.at[wr_page, wr_off].set(vq)
            cks = cache["k_scale"].at[wr_page, wr_off].set(ksc)
            cvs = cache["v_scale"].at[wr_page, wr_off].set(vsc)
        else:
            ck = ck.at[wr_page, wr_off].set(k[:, 0].astype(ck.dtype))
            cv = cv.at[wr_page, wr_off].set(v[:, 0].astype(cv.dtype))
        kpos = cache["kpos"].at[wr_page, wr_off].set(kpos_val)
        # the page-gathering kernels only route compiled: their grid walks
        # every lane's live pages (the int8 kernel's, every table entry)
        # and decode dispatches thousands of times, so the interpreter's
        # per-program overhead (~8x the jnp gather at serving shapes)
        # would dominate CPU serving — interpret CI
        # exercises the kernel bodies in tests/test_paged_kv.py and
        # tests/test_quant_kv.py; the jnp fallback is the gather oracle
        # (bitwise equal to the dense ref path on equal logical lengths)
        route = "pallas" if (impl == "pallas" and cfg.causal) else "ref"
        # plan-sharded serving (serve_exact hints context): the arena's
        # kv-head dim is partitioned over `model`, so the decode kernel
        # dispatch runs under shard_map — each model shard walks the
        # (replicated) page table over its own kv heads, the SPMD form of
        # the paper's per-head dotprod_softmax kernels behind the scatter
        # GMI.  Head counts that don't divide the axis run the whole call
        # on every device (the plan replicated the arena then too).
        ctx = paged_shard_ctx()
        mesh_kw = {} if ctx is None else {"mesh": ctx[0], "axis": ctx[1]}
        if quantized:
            out = kops.paged_flash_decode_q(
                qs[:, 0], ck, cv, cks, cvs, kpos, page_table, cpos,
                active=act, impl=route, **mesh_kw)[:, None]
            new_cache = {"k": ck, "v": cv, "k_scale": cks, "v_scale": cvs,
                         "kpos": kpos}
        else:
            out = kops.paged_flash_decode(
                qs[:, 0], ck.astype(q.dtype), cv.astype(q.dtype), kpos,
                page_table, cpos, active=act, impl=route,
                **mesh_kw)[:, None]
            new_cache = {"k": ck, "v": cv, "kpos": kpos}
    else:
        # decode: Sq == 1; the token's absolute position comes from the
        # model-level counter (positions[:, 0]) — the cache itself is
        # position-metadata-free apart from per-slot kpos.
        # Cache writes are vmapped per-row dynamic updates: a scatter whose
        # batch coord is a scattered dim would make SPMD replicate the whole
        # KV cache (observed 133 GB/chip on deepseek decode, §Perf 0.7).
        ck, cv = cache["k"], cache["v"]  # (B,slots,nkv,hd)
        cpos = positions[:, 0].astype(jnp.int32)
        slot = (cpos % ck.shape[1]) if window else jnp.minimum(
            cpos, ck.shape[1] - 1)

        def _dus(buf, start, val):
            return jax.lax.dynamic_update_slice_in_dim(
                buf, val[None], start, axis=0)

        ck = jax.vmap(_dus)(ck, slot, k[:, 0].astype(ck.dtype))
        cv = jax.vmap(_dus)(cv, slot, v[:, 0].astype(cv.dtype))
        kpos = jax.vmap(_dus)(cache["kpos"], slot, cpos)
        if (impl != "ref" and cfg.causal
                and kops.fused_grid_ok(impl, x.shape[0], nkv,
                                       -(-ck.shape[1] // 256))):
            # fused split-KV decode: one VMEM pass over the slot cache, GQA
            # grouped in-kernel (no repeated-KV reads), kpos sentinel and
            # ring-buffer window masked from the same absolute positions
            out = kops.flash_decode(
                qs[:, 0], ck.astype(q.dtype), cv.astype(q.dtype), kpos,
                cpos, window=window, impl=impl)[:, None]
        else:
            msk = _mask(1, ck.shape[1], positions, kpos, cfg.causal, window)
            out = _dense_attention(qs, ck.astype(q.dtype),
                                   cv.astype(q.dtype), msk)
        new_cache = {"k": ck, "v": cv, "kpos": kpos}

    # serve_exact plans gather the head outputs here (the Fig. 14 gather
    # GMI before linear_o) so the replicated wo contraction is bit-exact;
    # serve_psum plans keep them head-sharded so the column-sharded wo
    # contraction stays partial (one all-reduce); no-ops everywhere else
    out = hint(out.reshape(x.shape[0], x.shape[1], nh * hd), "gather")
    out = hint(out, "psum")
    wo = fsdp_int8_gather(p["wo"], tp_dim=0)
    return dense(out, wo), new_cache


def init_paged_attn_cache(cfg, num_pages: int, page_size: int,
                          dtype=COMPUTE_DTYPE, quantized: bool = False):
    """Paged KV arena: a global page pool instead of per-lane slot rows.

    No batch axis — lanes address the arena through their page tables, and
    capacity is shared: HBM scales with the pages actually allocated, not
    max_batch * worst-case slot length.  kpos starts at the never-written
    sentinel everywhere (including the reserved trash page 0), and the
    serving engine re-sentinels a page's kpos on reallocation, so a page's
    previous occupant is unreachable by construction.

    quantized=True stores int8 k/v plus per-row per-kv-head f32 scale
    planes (`k_scale`/`v_scale`, core/quant.kv_quantize): ~half the bytes
    per cache row, so a fixed HBM budget holds ~2x the pages.  The scales
    live in the arena — a radix-shared prefix page carries its scales with
    it, so every lane reading the page dequantizes identically.
    """
    assert not cfg.local_window, "paged KV does not support sliding windows"
    if cfg.kv_lora_rank:
        assert not quantized, "the latent arena has no int8 form"
        return {"ckv": jnp.zeros((num_pages, page_size, cfg.latent_stride),
                                 dtype),
                "kpos": jnp.full((num_pages, page_size), 2**30, jnp.int32)}
    kv_shape = (num_pages, page_size, cfg.n_kv_heads, cfg.head_dim)
    cache = {
        "k": jnp.zeros(kv_shape, jnp.int8 if quantized else dtype),
        "v": jnp.zeros(kv_shape, jnp.int8 if quantized else dtype),
        "kpos": jnp.full((num_pages, page_size), 2**30, jnp.int32),
    }
    if quantized:
        cache["k_scale"] = jnp.zeros(kv_shape[:3], jnp.float32)
        cache["v_scale"] = jnp.zeros(kv_shape[:3], jnp.float32)
    return cache


def init_attn_cache(cfg, batch: int, seq_len: int, dtype=COMPUTE_DTYPE):
    """KV cache; ring buffer of local_window slots when windowed."""
    slots = min(seq_len, cfg.local_window) if cfg.local_window else seq_len
    if cfg.kv_lora_rank:
        return {"ckv": jnp.zeros((batch, slots, cfg.latent_stride), dtype),
                "kpos": jnp.full((batch, slots), 2**30, jnp.int32)}
    return {
        "k": jnp.zeros((batch, slots, cfg.n_kv_heads, cfg.head_dim), dtype),
        "v": jnp.zeros((batch, slots, cfg.n_kv_heads, cfg.head_dim), dtype),
        # per-slot absolute positions; 2^30 marks never-written slots so the
        # causal mask excludes them (also excludes padded prompt columns)
        "kpos": jnp.full((batch, slots), 2**30, jnp.int32),
    }


# ---------------------------------------------------------------------------
# multi-head latent attention (DeepSeek-V2/V3)
# ---------------------------------------------------------------------------


def mla_init(key, cfg) -> Params:
    """q straight from x (no q LoRA); keys and values from one compressed
    row: wkv_a gives [c_kv | k_rope], c_kv is RMS-normed, and wkv_b
    expands it to each head's [k_nope | v]."""
    d, nh, r = cfg.d_model, cfg.n_heads, cfg.kv_lora_rank
    ks = jax.random.split(key, 4)
    return {
        "wq": dense_init(ks[0], d, nh * cfg.head_dim),
        "wkv_a": dense_init(ks[1], d, cfg.latent_row),
        "kv_norm": {"g": jnp.ones((r,), jnp.float32)},
        "wkv_b": dense_init(ks[2], r,
                            nh * (cfg.qk_nope_head_dim + cfg.v_head_dim)),
        "wo": dense_init(ks[3], nh * cfg.v_head_dim, d),
    }


def mla_attention(x, p, cfg, positions, segment_ids=None, cache=None,
                  page_table=None, active=None):
    """MLA block.  Every path writes and reads the same cache row per
    token, [RMSNorm(c_kv) | rope(k_rope) | zeros] (cfg.latent_row wide,
    stored cfg.latent_stride wide, bf16), so prefill buckets, prefix-hit
    pages and decode writes are one format.  Prefill (and training)
    expands the row per head: q.k over qk_nope + qk_rope dims, v of
    v_head_dim.  Decode is absorbed: q_nope is multiplied into W_UK to
    give a kv_lora_rank-wide latent query, the scores run against whole
    rows, the output is p . c_kv, and W_UV maps it to each head's v
    space, through the paged kernel's latent mode.  RoPE is rotate-half
    over the rope dims at the token's position (modeling_deepseek
    de-interleaves its rope
    columns first: with random weights the two differ by a fixed
    permutation of those columns).  Softmax scale 1/sqrt(head_dim)."""
    from repro.kernels import ops as kops
    from repro.models.layers import dense, rmsnorm

    b, s, _ = x.shape
    nh, r = cfg.n_heads, cfg.kv_lora_rank
    dn, dv = cfg.qk_nope_head_dim, cfg.v_head_dim
    q = dense(x, p["wq"]).reshape(b, s, nh, cfg.head_dim)
    q_nope = q[..., :dn]
    q_rope = apply_rope(q[..., dn:], positions, cfg.rope_theta)
    kva = dense(x, p["wkv_a"])
    ckv = rmsnorm(kva[..., :r], p["kv_norm"])
    k_rope = apply_rope(kva[..., None, r:], positions, cfg.rope_theta)
    pad = jnp.zeros((b, s, cfg.latent_stride - cfg.latent_row), ckv.dtype)
    row = jnp.concatenate([ckv, k_rope[:, :, 0], pad],
                          axis=-1).astype(COMPUTE_DTYPE)  # (B, S, stride)
    w_b = p["wkv_b"].reshape(r, nh, dn + dv)
    scale = 1.0 / math.sqrt(cfg.head_dim)

    if cache is None or (s > 1 and page_table is None):
        kv = jnp.einsum("bsr,rhe->bshe", row[..., :r], w_b)
        k = jnp.concatenate(
            [kv[..., :dn], jnp.broadcast_to(
                row[:, :, None, r:cfg.latent_row],
                (b, s, nh, cfg.qk_rope_head_dim))], axis=-1)
        qs = jnp.concatenate([q_nope, q_rope], axis=-1) * scale
        if s <= DENSE_ATTN_MAX_KV:
            msk = _mask(s, s, positions, positions, cfg.causal, 0,
                        segment_ids, segment_ids)
            out = _dense_attention(qs, k, kv[..., dn:], msk)
        else:
            out = _chunked_attention(qs, k, kv[..., dn:], positions,
                                     positions, cfg.causal, 0, segment_ids,
                                     segment_ids)
        new_cache = None
        if cache is not None:  # prefill: rows + positions into the bucket
            slots = cache["ckv"].shape[1]
            take = min(s, slots)
            idx = jnp.arange(take, dtype=jnp.int32)
            new_cache = {
                "ckv": cache["ckv"].at[:, idx].set(row[:, -take:]),
                "kpos": cache["kpos"].at[:, idx].set(
                    positions[:, -take:].astype(jnp.int32))}
    else:
        if s > 1 or page_table is None:
            raise NotImplementedError(
                "MLA decodes one token a step from the paged arena: it has "
                "no dense slot rows and no multi-query verify")
        q_lat = jnp.einsum("bhn,rhn->bhr", q_nope[:, 0], w_b[..., :dn])
        qa = jnp.concatenate([q_lat, q_rope[:, 0], jnp.zeros(
            (b, nh, cfg.latent_stride - cfg.latent_row), q_lat.dtype)],
            axis=-1) * scale  # the rows' zero pad scores 0
        cpos = positions[:, 0].astype(jnp.int32)
        act = (jnp.ones((b,), bool) if active is None
               else active.astype(bool))
        # the scatter and its trash-page redirect are the GQA paged path's;
        # the kernel loads each latent page once.  A cache with "layer"
        # holds every scanned layer's arena stacked (models/transformer.py
        # carries it through the layer scan): the rows go into that
        # layer's pages and the kernel reads them there, so no layer's
        # arena is sliced out or written back
        c, layer = cache["ckv"], cache.get("layer")
        at = () if layer is None else (layer,)
        ps = c.shape[-2]
        page = page_table[jnp.arange(b), cpos // ps]
        wr_page = jnp.where(act, page, 0)
        wr_off = jnp.where(act, cpos % ps, 0)
        c = c.at[(*at, wr_page, wr_off)].set(row[:, 0])
        kpos = cache["kpos"].at[(*at, wr_page, wr_off)].set(
            jnp.where(act, cpos, jnp.int32(2 ** 30)))
        route = "pallas" if kops.default_impl() == "pallas" else "ref"
        o_lat = kops.paged_flash_decode_latent(
            qa, c, kpos, page_table, cpos, r, active=act, layer=layer,
            impl=route)
        out = jnp.einsum("bhr,rhv->bhv", o_lat.astype(COMPUTE_DTYPE),
                         w_b[..., dn:])[:, None]
        new_cache = {"ckv": c, "kpos": kpos}
    out = out.reshape(b, s, nh * dv)
    return dense(out, p["wo"]), new_cache
