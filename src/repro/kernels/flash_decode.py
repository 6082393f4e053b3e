"""Pallas TPU kernel: split-KV single-query flash attention (decode).

Decode attention is the serving hot loop's memory-bound core: one query row
per slot against the whole KV cache.  The dense jnp path materializes the
(B, H, 1, S) score tensor in HBM and reads the GQA-expanded cache; this
kernel streams the cache through VMEM once — HBM traffic = K + V + Q + O,
with Q and O negligible (one row per head group).

Grid: (batch, Sk/bs) with the KV-split axis innermost, so each lane walks
its splits sequentially while every kv head's (m, l, acc) partials stay
resident in VMEM scratch; the final split runs the reduction epilogue
(normalize by l, cast, write O).  A block spans all kv heads of its rows:
its last two dims are the cache's own (KVH, hd), which Mosaic tiles for any
head count, and each row is read once.  All `q_per_kv` query heads of a KV
group ride in one block — the group dim is the sublane axis, so GQA costs
no extra cache reads.  Per-lane scalars (qpos, active, the page table) are
scalar-prefetched into SMEM.

Masking is slot-metadata driven, matching the serving cache contract
(models/attention.py):

  * `kpos` carries each cache slot's absolute position; the never-written
    sentinel (2^30) can never satisfy ``kpos <= qpos`` and is excluded by
    the causal test — no separate validity plane needed;
  * sliding windows test ``qpos - kpos < window`` against the same absolute
    positions, so ring-buffer caches (slot = pos % window) need no unrolling;
  * `active` gates whole rows: an inactive serving slot contributes an
    all-masked row and the epilogue emits exact zeros (l == 0), never NaN.

`paged_flash_decode` is the same online-softmax body over a *paged* KV
arena: the per-lane page table is scalar-prefetched and indexed inside the
BlockSpec index maps, so walking a lane's pages in logical order is just
the grid's DMA schedule — the gather costs nothing beyond the block
fetches the dense kernel already does, and radix-shared prefix pages are
fetched per lane that names them, never duplicated in HBM.  Its grid is a
work list of blocks of several pages, and a lane walks only the pages its
query can reach.  Its bf16 arena holds one row a token, each kv head's K
then its V, so a page is one DMA of whole rows; given a layer index it
reads one layer's pages of the stacked arena a layer scan carries.  The
int8 arena keeps separate (P, ps, KVH, hd) K and V and its own kernel,
`paged_flash_decode_q`.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BS = 256  # default KV split length (sublane dim of the k/v blocks)
NEG_INF = -1e30
KPOS_SENTINEL = 2 ** 30  # never-written cache slot (models/attention.py)
PAGED_BLOCK_BYTES = 1 << 20  # arena bytes one paged grid step holds


def _online_softmax_step(q, k, v, msk, m_ref, l_ref, acc_ref, h: int):
    """One key block of kv head `h` folded into that head's running
    (m, l, acc) partials — the online-softmax body every decode kernel
    here shares.  q: (G, hd); k/v: (n, hd); msk: broadcastable to (G, n)."""
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)  # (G, n)
    s = jnp.where(msk, s, NEG_INF)
    m_prev = m_ref[h]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    # p from the mask, not from s > threshold: a fully-masked block leaves
    # m_new at NEG_INF and exp(s - m_new) would be exp(0) = 1 garbage
    p = jnp.where(msk, jnp.exp(s - m_new), 0.0)
    alpha = jnp.exp(m_prev - m_new)
    l_new = l_ref[h] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    pv = jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_ref[h] = m_new
    l_ref[h] = l_new
    acc_ref[h] = acc_ref[h] * alpha + pv


def _init_partials(m_ref, l_ref, acc_ref):
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)


def _write_out(o_ref, l_ref, acc_ref, kvh: int):
    # combine the split partials: normalize by the running l.  l == 0
    # (inactive slot / fresh cache, every key masked) yields exact 0.
    for h in range(kvh):
        o_ref[0, h] = (acc_ref[h]
                       / jnp.maximum(l_ref[h], 1e-30)).astype(o_ref.dtype)


def _kernel(qpos_ref, active_ref, q_ref, k_ref, v_ref, kpos_ref, o_ref,
            m_ref, l_ref, acc_ref, *, n_s: int, window: int):
    b, j = pl.program_id(0), pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        _init_partials(m_ref, l_ref, acc_ref)

    qpos = qpos_ref[b]
    kpos = kpos_ref[0]  # (1, bs) absolute positions; 2^30 = never written
    msk = kpos <= qpos  # causal; also rejects the sentinel
    if window:
        msk &= qpos - kpos < window
    msk &= active_ref[b] != 0
    for h in range(q_ref.shape[1]):  # every kv head of the block
        _online_softmax_step(q_ref[0, h], k_ref[0, :, h, :],
                             v_ref[0, :, h, :], msk, m_ref, l_ref, acc_ref,
                             h)

    @pl.when(j == n_s - 1)
    def _epilogue():
        _write_out(o_ref, l_ref, acc_ref, q_ref.shape[1])


def _live_pages(qpos, active, ps: int, maxp: int):
    """Pages lane b's query can reach: page j holds positions
    [j*ps, (j+1)*ps), so every page past qpos // ps holds only keys with
    kpos > qpos or sentinels; an inactive lane reaches none."""
    return jnp.where(active != 0, jnp.minimum(maxp, qpos // ps + 1), 0)


def _paged_kernel(pt_ref, qpos_ref, active_ref, lane_ref, blk_ref, col_ref,
                  *refs, ppb: int, maxp: int, latent: int = 0,
                  stacked: bool = False):
    """Grid step t: block blk_ref[t] of lane lane_ref[t]'s live pages,
    `ppb` pages folded into the online softmax at once.  Refs after the
    scalars: q, ppb arena pages, ppb kpos rows (the pages col_ref named),
    then o and the partials.  A lane with no live page has one step,
    which writes its zeros.  Each page is one DMA of whole rows.  Dense
    mode: a row holds each kv head's K then its V, head after head, and
    head h's keys and values are sliced from the block already loaded.
    Latent mode (`latent` = the value width): one row a key, its values
    the row's first `latent` columns.  `stacked`: the layer index is one
    more scalar ref, before q."""
    if stacked:
        refs = refs[1:]
    q_ref, kv_refs = refs[0], refs[1:ppb + 1]
    kp_refs = refs[ppb + 1:2 * ppb + 1]
    o_ref, m_ref, l_ref, acc_ref = refs[2 * ppb + 1:]
    t = pl.program_id(0)
    b, i = lane_ref[t], blk_ref[t]
    ps = kp_refs[0].shape[-1]
    hd = q_ref.shape[-1]
    n = _live_pages(qpos_ref[b], active_ref[b], ps, maxp)

    @pl.when(i == 0)
    def _init():
        _init_partials(m_ref, l_ref, acc_ref)

    @pl.when(n > 0)
    def _block():
        width = ppb * ps
        kpos = jnp.concatenate([r[0] for r in kp_refs], axis=1)  # (1, width)
        # slots past the last live page hold another block's page or
        # page 0: out by position, whatever they hold
        live = n * ps - i * width
        col = jax.lax.broadcasted_iota(jnp.int32, (1, width), 1)
        row = jax.lax.broadcasted_iota(jnp.int32, (width, 1), 0)
        msk = (kpos <= qpos_ref[b]) & (col < live)
        # whole pages, loaded once: a head sliced out of a loaded block
        # costs far less than a strided load of it from each ref
        x = jnp.concatenate([r[0] for r in kv_refs], axis=0)  # (width, R)
        for h in range(q_ref.shape[1]):
            if latent:
                k, v = x, x[:, :latent]
            else:
                k = x[:, 2 * h * hd:(2 * h + 1) * hd]
                v = x[:, (2 * h + 1) * hd:(2 * h + 2) * hd]
            # p is 0 there, but 0 * NaN is not: stale values stay out
            v = jnp.where(row < live, v, jnp.zeros_like(v))
            _online_softmax_step(q_ref[0, h], k, v, msk, m_ref, l_ref,
                                 acc_ref, h)

    @pl.when((i + 1) * ppb >= n)
    def _epilogue():
        _write_out(o_ref, l_ref, acc_ref, q_ref.shape[1])


def _work_list(qpos, active, ps: int, maxp: int, ppb: int):
    """The grid's steps: each lane's blocks of live pages in lane order,
    one step for a lane with none.  Returns the step count, the lane and
    the block of step t, and the page-table column of each block slot.
    A slot past the lane's last live page names the column it named a
    block before (-1, page 0, in a lane's first block), so the pipeline
    copies only the pages that changed since the step before.  Every
    array is padded past the step count to the most steps any call can
    have."""
    b = qpos.shape[0]
    n = _live_pages(qpos, active, ps, maxp)
    nblk = jnp.maximum(1, -(-n // ppb))
    end = jnp.cumsum(nblk)
    t = jnp.arange(b * pl.cdiv(maxp, ppb) + 1, dtype=jnp.int32)
    lanes = jnp.minimum(jnp.sum(t[:, None] >= end[None, :], axis=1), b - 1)
    mine = lanes[:, None] == jnp.arange(b)[None, :]
    blks = t - jnp.sum(jnp.where(mine, end - nblk, 0), axis=1)
    n_t = jnp.sum(jnp.where(mine, n, 0), axis=1)[:, None]
    j = blks[:, None] * ppb + jnp.arange(ppb)[None, :]
    j = jnp.where(j < n_t, j, j - ppb)
    cols = jnp.where((j >= 0) & (j < n_t), j, -1)
    as32 = lambda x: x.astype(jnp.int32)  # noqa: E731
    return end[-1], as32(lanes), as32(blks), as32(cols.reshape(-1))


def pages_per_block(ps: int, row_bytes: int, maxp: int) -> int:
    """Pages a grid step folds in: enough keys to fill the 128 lanes of
    the score tile, no more arena bytes than PAGED_BLOCK_BYTES (a page
    is ps rows of `row_bytes`: every kv head's K and V, or one latent
    row), and no more than a lane's table."""
    return max(1, min(maxp, 128 // ps, PAGED_BLOCK_BYTES // (ps * row_bytes)))


def _paged_kernel_q(pt_ref, qpos_ref, active_ref, q_ref, k_ref, v_ref,
                    ks_ref, vs_ref, kpos_ref, o_ref, m_ref, l_ref, acc_ref,
                    *, n_p: int):
    """The online-softmax body over an *int8* KV arena, one page a grid
    step over every table entry (grid (B, MAXP)): the page named by
    pt[b, j] arrives as int8 k/v tiles plus their per-row f32 scales, and
    the tiles are dequantized in VMEM right before the dots — HBM moves
    ~half the bytes of the bf16 arena (int8 values + one f32 scale per row
    per kv head) while the online-softmax recurrence is unchanged and
    stays f32."""
    b, j = pl.program_id(0), pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        _init_partials(m_ref, l_ref, acc_ref)

    qpos = qpos_ref[b]
    kpos = kpos_ref[0]  # (1, ps) absolute positions; 2^30 = never written
    msk = (kpos <= qpos) & (active_ref[b] != 0)
    for h in range(q_ref.shape[1]):
        # (ps, hd) int8 * (ps, 1) f32 scale -> dequantized page tile in
        # VMEM; the scales ride the same page-table indirection as kpos, so
        # a radix-shared page dequantizes identically for every lane
        k = k_ref[0, :, h, :].astype(jnp.float32) * ks_ref[0, :, h:h + 1]
        v = v_ref[0, :, h, :].astype(jnp.float32) * vs_ref[0, :, h:h + 1]
        _online_softmax_step(q_ref[0, h].astype(jnp.float32), k, v, msk,
                             m_ref, l_ref, acc_ref, h)

    @pl.when(j == n_p - 1)
    def _epilogue():
        _write_out(o_ref, l_ref, acc_ref, q_ref.shape[1])


def _scratch(kvh: int, g: int, hd: int):
    return [
        pltpu.VMEM((kvh, g, 1), jnp.float32),   # running max m
        pltpu.VMEM((kvh, g, 1), jnp.float32),   # running sum l
        pltpu.VMEM((kvh, g, hd), jnp.float32),  # output accumulator
    ]


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_flash_decode_q(q: jax.Array, k: jax.Array, v: jax.Array,
                         k_scale: jax.Array, v_scale: jax.Array,
                         kpos: jax.Array, page_table: jax.Array,
                         qpos: jax.Array, active: jax.Array, *,
                         interpret: bool = False) -> jax.Array:
    """Split-KV decode over a *quantized* (int8) paged KV arena.

    Same contract as `paged_flash_decode` except k/v are int8 arenas and
    k_scale/v_scale: (P, ps, KVH) f32 carry one symmetric scale per cache
    row per kv head (core/quant.kv_quantize).  Scales are fetched through
    the same scalar-prefetched page-table indirection as the kpos plane,
    and the tiles are dequantized in VMEM just before the dots, so the
    kernel's HBM traffic is the int8 bytes + scales — ~half the bf16
    arena's — while the softmax recurrence runs in f32 exactly like the
    unquantized kernel.
    """
    b, kvh, g, hd = q.shape
    n_pages, ps = kpos.shape
    maxp = page_table.shape[1]
    kern = functools.partial(_paged_kernel_q, n_p=maxp)
    page = lambda b, j, pt, qp, act: (pt[b, j], 0, 0, 0)  # noqa: E731
    scale_spec = pl.BlockSpec((1, ps, kvh), lambda b, j, pt, qp, act:
                              (pt[b, j], 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, maxp),
        in_specs=[
            pl.BlockSpec((1, kvh, g, hd), lambda b, j, pt, qp, act:
                         (b, 0, 0, 0)),
            pl.BlockSpec((1, ps, kvh, hd), page),  # k int8
            pl.BlockSpec((1, ps, kvh, hd), page),  # v int8
            scale_spec,  # k_scale
            scale_spec,  # v_scale
            pl.BlockSpec((1, 1, ps), lambda b, j, pt, qp, act:
                         (pt[b, j], 0, 0)),  # kpos
        ],
        out_specs=pl.BlockSpec((1, kvh, g, hd), lambda b, j, pt, qp, act:
                               (b, 0, 0, 0)),
        scratch_shapes=_scratch(kvh, g, hd),
    )
    return pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kvh, g, hd), q.dtype),
        interpret=interpret,
    )(page_table, qpos, active, q, k, v,
      k_scale, v_scale,
      kpos.reshape(n_pages, 1, ps))


@functools.partial(jax.jit, static_argnames=("ppb", "latent", "interpret"))
def paged_flash_decode(q: jax.Array, kv: jax.Array, kpos: jax.Array,
                       page_table: jax.Array, qpos: jax.Array,
                       active: jax.Array, layer=None, *, ppb: int,
                       latent: int = 0, interpret: bool = False
                       ) -> jax.Array:
    """Split-KV decode over a *paged* KV arena.

    q: (B, KVH, G, hd) pre-scaled grouped queries; kv: (P, ps,
    2*KVH*hd) global page arena shared by every lane, one row a token
    holding each kv head's K then its V, head after head ([k_0 | v_0 |
    k_1 | v_1 | ...]); kpos: (P, ps) int32 absolute positions per arena
    slot (2^30 = never written); page_table: (B, MAXP) int32 — lane b's
    logical KV positions [j*ps, (j+1)*ps) live in arena page
    page_table[b, j].  Entries may repeat across lanes (radix-shared
    prefixes) and unused entries may point anywhere whose kpos are all
    sentinel (the allocator's trash page 0).  qpos: (B,) int32; active:
    (B,) int32 row gate.  Returns (B, KVH, G, hd).

    Grid: one step per block of `ppb` live pages (ops.py derives ppb from
    the shapes, `pages_per_block`), lane after lane.  Lane b's live pages
    are the min(MAXP, qpos // ps + 1) its query can reach, none when it
    is inactive: the walk stops there, and a lane with none gets one step
    that writes its zeros.  The page table, qpos, active and the work
    list are scalar-prefetched.  Each of a block's ppb pages is one arena
    and one kpos operand whose index map reads the page table, so the
    per-page DMA *is* the gather — one DMA of whole rows a page, every kv
    head's K and V in it — and the pipeline starts the next block's
    copies before this block's compute, across lanes too.  A lane's row
    depends on its own qpos, active and table row alone.

    Latent mode (`latent` > 0, KVH 1): kv is a (P, ps, hd) arena of MLA
    rows, and a key's values are its row's first `latent` columns.
    Returns (B, 1, G, latent).

    With `layer` (an int32 scalar) kv is the (L, P, ps, R) arena of every
    layer a scan steps through, and the kernel reads layer `layer`'s
    pages where they lie: the caller writes the step's rows into the
    stacked arena and never slices a layer out.  kpos is then either
    stacked too, (L, P, ps) (latent mode), or that layer's own (P, ps)
    plane.
    """
    b, kvh, g, hd = q.shape
    stacked = layer is not None
    kpos_stacked = kpos.ndim == 3
    assert stacked or not kpos_stacked, kpos.shape
    n_pages, ps = kpos.shape[-2:]
    maxp = page_table.shape[1]
    dv = latent or hd
    steps, lanes, blks, cols = _work_list(qpos, active, ps, maxp, ppb)
    lane = lambda t, *s: (s[3][t], 0, 0, 0)  # noqa: E731

    def page(c, layered):  # block slot c's (layer and) page at grid step t
        def index(t, pt, qp, act, ln, bk, col, *lyr):
            j = col[t * ppb + c]
            p = jnp.where(j >= 0, pt[ln[t], jnp.maximum(j, 0)], 0)
            return (lyr[0][0], p) if layered else (p,)
        return index

    def pages(shape, layered):  # one operand a block slot
        at = (None,) if layered else ()  # the layer axis, squeezed
        return [pl.BlockSpec((*at, 1, *shape),
                             lambda *a, f=page(c, layered): (*f(*a), 0, 0))
                for c in range(ppb)]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=7 if stacked else 6,
        grid=(steps,),
        in_specs=[pl.BlockSpec((1, kvh, g, hd), lane),
                  *pages((ps, kv.shape[-1]), stacked),
                  *pages((1, ps), kpos_stacked)],
        out_specs=pl.BlockSpec((1, kvh, g, dv), lane),
        scratch_shapes=_scratch(kvh, g, dv),
    )
    kp3 = kpos.reshape(*kpos.shape[:-1], 1, ps)
    lyr = (jnp.asarray(layer, jnp.int32).reshape(1),) if stacked else ()
    return pl.pallas_call(
        functools.partial(_paged_kernel, ppb=ppb, maxp=maxp, latent=latent,
                          stacked=stacked),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kvh, g, dv), q.dtype),
        interpret=interpret,
        name="paged_flash_decode",
    )(page_table, qpos, active, lanes, blks, cols, *lyr, q, *[kv] * ppb,
      *[kp3] * ppb)


@functools.partial(
    jax.jit, static_argnames=("window", "bs", "interpret"))
def flash_decode(q: jax.Array, k: jax.Array, v: jax.Array,
                 kpos: jax.Array, qpos: jax.Array, active: jax.Array, *,
                 window: int = 0, bs: int = BS,
                 interpret: bool = False) -> jax.Array:
    """q: (B, KVH, G, hd) pre-scaled grouped queries; k/v: (B, Sk, KVH, hd).

    kpos: (B, Sk) int32 absolute key positions (2^30 = never written);
    qpos: (B,) int32 query position; active: (B,) int32 row gate.
    Sk % bs == 0 (ops.py pads with the kpos sentinel).  Returns
    (B, KVH, G, hd) in q.dtype.  ops.py handles layout, padding and GQA
    head-group reshapes.
    """
    b, kvh, g, hd = q.shape
    sk = k.shape[1]
    assert sk % bs == 0, (sk, bs)
    n_s = sk // bs
    kern = functools.partial(_kernel, n_s=n_s, window=window)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, n_s),
        in_specs=[
            pl.BlockSpec((1, kvh, g, hd), lambda b, j, qp, act:
                         (b, 0, 0, 0)),
            pl.BlockSpec((1, bs, kvh, hd), lambda b, j, qp, act:
                         (b, j, 0, 0)),
            pl.BlockSpec((1, bs, kvh, hd), lambda b, j, qp, act:
                         (b, j, 0, 0)),
            pl.BlockSpec((1, 1, bs), lambda b, j, qp, act: (b, 0, j)),
        ],
        out_specs=pl.BlockSpec((1, kvh, g, hd), lambda b, j, qp, act:
                               (b, 0, 0, 0)),
        scratch_shapes=_scratch(kvh, g, hd),
    )
    return pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kvh, g, hd), q.dtype),
        interpret=interpret,
    )(qpos, active, q, k, v, kpos.reshape(b, 1, sk))
