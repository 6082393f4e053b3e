"""Public jit'd wrappers around the Pallas kernels.

Dispatch policy: on TPU backends the Pallas kernels run compiled; elsewhere
they run in interpret mode (exact same kernel body, executed by the Pallas
interpreter) or fall back to the pure-jnp oracle (`impl="ref"`).  All
wrappers apply the paper's *minimum padding* rule (§7.1): operands are padded
only up to the tile granularity the hardware needs (MXU 128 lanes here,
NUM_PE there) and the padding is stripped from the result.
"""
from __future__ import annotations

import contextlib
import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.ibert_ops import LNParams
from repro.kernels import ref as _ref
from repro.kernels import int8_matmul as _mm
from repro.kernels import i_gelu as _ig
from repro.kernels import i_layernorm as _iln
from repro.kernels import i_softmax as _ism

_IMPL = None


def default_impl() -> str:
    """'pallas' on TPU, 'interpret' on CPU unless overridden.

    Interpret mode runs the *same kernel bodies* through the Pallas
    interpreter, so CPU CI exercises the real kernels; the pure-jnp oracle
    stays reachable via ``set_impl("ref")`` (or per-call ``impl="ref"``).
    """
    global _IMPL
    if _IMPL is None:
        _IMPL = "pallas" if jax.default_backend() == "tpu" else "interpret"
    return _IMPL


def set_impl(impl: str) -> None:
    global _IMPL
    assert impl in ("pallas", "interpret", "ref")
    _IMPL = impl


@contextlib.contextmanager
def pinned_impl(impl: str):
    """Pin the process-wide impl inside a block, restoring the previous
    value (including the unresolved None) on exit — the benches and tests
    that compare token streams across engines pin one impl on both sides
    (docs/perf.md §impl selection)."""
    global _IMPL
    prev = _IMPL
    set_impl(impl)
    try:
        yield
    finally:
        _IMPL = prev


# interpret mode replays the grid at trace time (one kernel-body trace per
# program), so routing decisions must bound the grid: ~2ms/program means
# 1024 keeps first-call latency under a few seconds for CPU CI while the
# 32k dry-run cells (10^5+ programs) fall back to the jnp paths.
INTERPRET_MAX_GRID = 1024


def fused_grid_ok(impl: str, *dims: int) -> bool:
    """Is a Pallas kernel with this grid routable under `impl`?"""
    if impl == "pallas":
        return True
    n = 1
    for d in dims:
        n *= d
    return n <= INTERPRET_MAX_GRID


def _pad_to(x: jax.Array, mult, axis: int, value=0) -> jax.Array:
    size = x.shape[axis]
    pad = (-size) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


def int8_matmul(a: jax.Array, b: jax.Array, s_a, s_b,
                s_out=None, bias: Optional[jax.Array] = None,
                impl: Optional[str] = None) -> jax.Array:
    """Minimum-padded INT8 GEMM (+bias at s_a*s_b, + optional requant to s_out)."""
    impl = impl or default_impl()
    if impl == "ref":
        return _ref.int8_matmul(a, b, s_a, s_b, bias=bias, s_out=s_out)
    m, k = a.shape
    _, n = b.shape
    bm, bn = min(_mm.BM, _rup(m, 8)), min(_mm.BN, _rup(n, 128))
    bk = min(_mm.BK, _rup(k, 128))
    ap = _pad_to(_pad_to(a, bm, 0), bk, 1)
    bp = _pad_to(_pad_to(b, bk, 0), bn, 1)
    biasp = _pad_to(bias, bn, 0) if bias is not None else None
    out = _mm.int8_matmul(
        ap, bp, jnp.asarray(s_a, jnp.float32), jnp.asarray(s_b, jnp.float32),
        s_out=None if s_out is None else jnp.asarray(s_out, jnp.float32),
        bias=biasp, bm=bm, bn=bn, bk=bk,
        requant=s_out is not None, interpret=impl == "interpret",
    )
    return out[:m, :n]


def _rup(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def i_gelu(q: jax.Array, scale, impl: Optional[str] = None) -> jax.Array:
    impl = impl or default_impl()
    if impl == "ref":
        return _ref.i_gelu_elem(q, scale)
    shape = q.shape
    q2 = q.reshape(-1, shape[-1])
    rows = q2.shape[0]
    br = min(_ig.BLOCK_ROWS, rows)
    q2 = _pad_to(q2, br, 0)
    out = _ig.i_gelu(q2, jnp.asarray(scale, jnp.float32), block_rows=br,
                     interpret=impl == "interpret")
    return out[:rows].reshape(shape)


def i_softmax(q: jax.Array, scale, impl: Optional[str] = None) -> jax.Array:
    """Integer softmax over last axis -> int32 probs at 2^-14."""
    impl = impl or default_impl()
    if impl == "ref":
        return _ref.i_softmax_rows(q, scale)
    shape = q.shape
    q2 = q.reshape(-1, shape[-1])
    rows = q2.shape[0]
    br = min(_ism.BLOCK_ROWS, rows)
    q2 = _pad_to(q2, br, 0)
    out = _ism.i_softmax(q2, jnp.asarray(scale, jnp.float32), block_rows=br,
                         interpret=impl == "interpret")
    return out[:rows].reshape(shape)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    causal: bool = True,
                    impl: Optional[str] = None) -> jax.Array:
    """Fused flash attention. q:(B,S,H,hd), k/v:(B,S,KVH,hd) -> (B,S,H,hd).

    GQA is handled by repeating per-head views into the kernel's flattened
    (B*H, S, hd) layout (views, not materialized copies, on TPU); minimum
    padding to tile multiples per the paper's NUM_PE rule."""
    impl = impl or default_impl()
    b, s, h, hd = q.shape
    kvh = k.shape[2]
    if impl == "ref":
        scale = 1.0 / (hd ** 0.5)  # the oracle expects pre-scaled q
        out = jax.vmap(jax.vmap(
            lambda qq, kk, vv: _ref.flash_attention(qq * scale, kk, vv,
                                                    causal),
            in_axes=(1, 1, 1), out_axes=1))(
                q, jnp.repeat(k, h // kvh, axis=2),
                jnp.repeat(v, h // kvh, axis=2))
        return out
    from repro.kernels import flash_attention as _fa

    g = h // kvh
    qf = q.transpose(0, 2, 1, 3).reshape(b * h, s, hd)
    kf = jnp.repeat(k, g, axis=2).transpose(0, 2, 1, 3).reshape(b * h, s, hd)
    vf = jnp.repeat(v, g, axis=2).transpose(0, 2, 1, 3).reshape(b * h, s, hd)
    bq = min(_fa.BQ, _rup(s, 8))
    bk = min(_fa.BK, _rup(s, 8))
    pad = (-s) % bq
    if pad:
        qf = jnp.pad(qf, ((0, 0), (0, pad), (0, 0)))
        kf = jnp.pad(kf, ((0, 0), (0, pad), (0, 0)))
        vf = jnp.pad(vf, ((0, 0), (0, pad), (0, 0)))
    out = _fa.flash_attention(qf, kf, vf, causal=causal, bq=bq, bk=bk,
                              kv_len=s, interpret=impl == "interpret")
    out = out[:, :s].reshape(b, h, s, hd).transpose(0, 2, 1, 3)
    return out


def flash_decode(q: jax.Array, k: jax.Array, v: jax.Array,
                 kpos: jax.Array, qpos: jax.Array,
                 active: Optional[jax.Array] = None,
                 window: int = 0, bs: Optional[int] = None,
                 impl: Optional[str] = None) -> jax.Array:
    """Split-KV single-query (decode) attention over a slot KV cache.

    q: (B, H, hd) *pre-scaled* by 1/sqrt(hd) (both impls — unlike
    `flash_attention`, whose kernel scales internally); k/v: (B, Sk, KVH,
    hd); kpos: (B, Sk) int32 absolute positions (2^30 = never-written
    sentinel); qpos: (B,) int32; active: optional (B,) bool slot gate;
    window: sliding-window width (0 = none); bs: KV split length.
    Returns (B, H, hd) in q.dtype.
    """
    impl = impl or default_impl()
    if impl == "ref":
        return _ref.flash_decode(q, k, v, kpos, qpos, active=active,
                                 window=window)
    from repro.kernels import flash_decode as _fd

    b, h, hd = q.shape
    sk, kvh = k.shape[1], k.shape[2]
    g = h // kvh
    bs = bs or min(_fd.BS, _rup(sk, 8))
    qg = q.reshape(b, kvh, g, hd)
    gp = _rup(g, 8)  # group dim is the sublane axis: pad to tile granularity
    if gp != g:
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, gp - g), (0, 0)))
    pad = (-sk) % bs
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        # pad keys carry the never-written sentinel: masked, not attended
        kpos = jnp.pad(kpos, ((0, 0), (0, pad)),
                       constant_values=_fd.KPOS_SENTINEL)
    act = (jnp.ones((b,), jnp.int32) if active is None
           else active.astype(jnp.int32).reshape(b))
    out = _fd.flash_decode(
        qg, k, v, kpos.astype(jnp.int32), qpos.astype(jnp.int32), act,
        window=window, bs=bs, interpret=impl == "interpret")
    return out[:, :, :g].reshape(b, h, hd)


def _shard_map_heads(call, mesh, axis, q, arenas, rest):
    """Dispatch a paged-decode call under shard_map with the kv-head axis
    of q and of the `arenas` leaves partitioned over `axis` and `rest`
    (positions, page table, lane gate) replicated.

    `q` is (B, H, hd) with heads laid out kvh-major (ops reshape to
    (B, KVH, G, hd) below), so a contiguous H split is exactly a KV-head
    split — each shard holds whole GQA groups and computes its heads'
    outputs locally; per-head math (online softmax over that head's pages)
    never crosses the axis, which is what keeps the sharded dispatch
    bit-identical to the unsharded one.  This is the SPMD form of the
    paper's scatter-GMI -> per-head kernels -> gather-GMI pipeline stage.

    Head counts that don't divide the axis keep every operand replicated
    (the plan replicated the arena then too): each device runs the whole
    call.  It still goes through shard_map because the partitioner cannot
    split a Mosaic kernel by itself.
    """
    from jax.sharding import PartitionSpec as P

    n = mesh.shape[axis]
    if q.shape[1] % n or arenas[0].shape[2] % n:
        axis = None
    return jax.shard_map(
        call, mesh=mesh,
        in_specs=((P(None, axis),) + (P(None, None, axis),) * len(arenas)
                  + (P(),) * len(rest)),
        out_specs=P(None, axis), check_vma=False,
    )(q, *arenas, *rest)


def paged_flash_decode(q: jax.Array, k: jax.Array, v: jax.Array,
                       kpos: jax.Array, page_table: jax.Array,
                       qpos: jax.Array,
                       active: Optional[jax.Array] = None,
                       impl: Optional[str] = None,
                       mesh=None, axis: Optional[str] = None) -> jax.Array:
    """Single-query decode attention over a paged KV arena.

    q: (B, H, hd) *pre-scaled* by 1/sqrt(hd); k/v: (P, ps, KVH, hd) global
    page arenas; kpos: (P, ps) int32 absolute positions (2^30 =
    never-written sentinel); page_table: (B, MAXP) int32 mapping lane b's
    logical page j to an arena page (entries may repeat across lanes —
    radix-shared prefixes; unused entries must name pages whose kpos are
    all sentinel, e.g. the allocator's trash page 0); qpos: (B,) int32;
    active: optional (B,) bool lane gate.  Returns (B, H, hd) in q.dtype.

    Unlike the dense wrapper there is no KV padding to do: pages are the
    tile granularity already.  Sliding windows aren't supported here — the
    serving engine keeps windowed (ring-buffer) caches on the dense slot
    path.

    mesh/axis: dispatch under shard_map with the arena's kv-head dim (and
    q's head dim) partitioned over `axis` when the head counts divide it
    and kpos/page_table/qpos/active replicated — the plan-sharded serving
    path (`_shard_map_heads`).
    """
    impl = impl or default_impl()
    if mesh is not None and axis is not None and mesh.shape[axis] > 1:
        b = q.shape[0]
        act = (jnp.ones((b,), bool) if active is None
               else active.astype(bool))

        def call(qb, kb, vb, kpb, ptb, qpb, ab):
            return paged_flash_decode(qb, kb, vb, kpb, ptb, qpb,
                                      active=ab, impl=impl)

        return _shard_map_heads(call, mesh, axis, q, (k, v),
                                (kpos, page_table, qpos, act))
    if impl == "ref":
        return _ref.paged_flash_decode(q, k, v, kpos, page_table, qpos,
                                       active=active)
    from repro.kernels import flash_decode as _fd

    b, h, hd = q.shape
    kvh = k.shape[2]
    g = h // kvh
    qg = q.reshape(b, kvh, g, hd)
    gp = _rup(g, 8)  # group dim is the sublane axis: pad to tile granularity
    if gp != g:
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, gp - g), (0, 0)))
    act = (jnp.ones((b,), jnp.int32) if active is None
           else active.astype(jnp.int32).reshape(b))
    ppb = _fd.pages_per_block(k.shape[1], kvh, hd, k.dtype.itemsize,
                              page_table.shape[1])
    out = _fd.paged_flash_decode(
        qg, k, v, kpos.astype(jnp.int32), page_table.astype(jnp.int32),
        qpos.astype(jnp.int32), act, ppb=ppb, interpret=impl == "interpret")
    return out[:, :, :g].reshape(b, h, hd)


def paged_flash_decode_latent(q: jax.Array, c: jax.Array, kpos: jax.Array,
                              page_table: jax.Array, qpos: jax.Array,
                              dv: int, active: Optional[jax.Array] = None,
                              layer=None,
                              impl: Optional[str] = None) -> jax.Array:
    """Absorbed MLA decode attention over a latent paged arena.

    q: (B, H, R) *pre-scaled* latent queries ([q_nope . W_UK | rope(q_rope)]);
    c: (P, ps, R) arena of cache rows [RMSNorm(c_kv) | rope(k_rope)],
    one a token, shared by every head; each key's values are its row's
    first `dv` columns (c_kv).  Otherwise `paged_flash_decode`'s contract:
    the H heads ride one group over the single latent "kv head", and the
    kernel loads each page once (its latent mode).  With `layer` (int32
    scalar) c is (L, P, ps, R) and kpos (L, P, ps), every layer's
    arena stacked, and layer `layer` is attended in place.  Returns
    (B, H, dv).
    """
    impl = impl or default_impl()
    if impl == "ref":
        if layer is not None:
            c, kpos = c[layer], kpos[layer]
        return _ref.paged_flash_decode_latent(q, c, kpos, page_table, qpos,
                                              dv, active=active)
    from repro.kernels import flash_decode as _fd

    b, h, r = q.shape
    qg = q.reshape(b, 1, h, r)
    gp = _rup(h, 8)  # group dim is the sublane axis: pad to tile granularity
    if gp != h:
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, gp - h), (0, 0)))
    act = (jnp.ones((b,), jnp.int32) if active is None
           else active.astype(jnp.int32).reshape(b))
    ppb = _fd.pages_per_block(c.shape[-2], 1, r, c.dtype.itemsize,
                              page_table.shape[1], arenas=1)
    out = _fd.paged_flash_decode(
        qg, c, None, kpos.astype(jnp.int32), page_table.astype(jnp.int32),
        qpos.astype(jnp.int32), act, layer, ppb=ppb, latent=dv,
        interpret=impl == "interpret")
    return out[:, 0, :h]


def expert_gmm(lhs: jax.Array, rhs: jax.Array, group_sizes: jax.Array,
               offset: int, impl: Optional[str] = None) -> jax.Array:
    """Grouped matmul over the held experts: lhs (M, K) rows sorted by
    expert, group_sizes (E,) rows of every expert, rhs (G, K, N) experts
    offset..offset+G-1.  Rows of other experts, and rows past every
    group, return 0.  The kernel is megablox's `gmm` with `group_offset`
    (an expert's weights stream through VMEM once per row tile its group
    touches; an expert with no rows is never read); it names its Pallas
    kernel `kernel`, so a device trace shows its calls as `kernel.<n>`.
    M is padded here to the row tile."""
    impl = impl or default_impl()
    if impl == "ref":
        return _ref.expert_gmm(lhs, rhs, group_sizes, offset)
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    m, k = lhs.shape
    n = rhs.shape[2]
    tm = min(128, _rup(m, 16))
    # a k or n block is 512 wide where that divides, else the whole dim
    tiles = (tm, 512 if k % 512 == 0 else k, 512 if n % 512 == 0 else n)
    sizes = group_sizes.astype(jnp.int32)
    out = gmm(_pad_to(lhs, tm, 0), rhs, sizes,
              preferred_element_type=lhs.dtype, tiling=tiles,
              group_offset=jnp.int32(offset),
              interpret=impl == "interpret")[:m]
    ends = jnp.cumsum(sizes)
    row = jnp.arange(m)[:, None]
    held = ((row >= ends[offset] - sizes[offset])
            & (row < ends[offset + rhs.shape[0] - 1]))
    return jnp.where(held, out, jnp.zeros_like(out))


def paged_flash_decode_q(q: jax.Array, k: jax.Array, v: jax.Array,
                         k_scale: jax.Array, v_scale: jax.Array,
                         kpos: jax.Array, page_table: jax.Array,
                         qpos: jax.Array,
                         active: Optional[jax.Array] = None,
                         impl: Optional[str] = None,
                         mesh=None, axis: Optional[str] = None) -> jax.Array:
    """Single-query decode attention over a *quantized* (int8) paged arena.

    Same contract as `paged_flash_decode` with k/v int8 and
    k_scale/v_scale: (P, ps, KVH) f32 per-row per-kv-head symmetric scales
    (core/quant.kv_quantize).  Scales live in the arena and are gathered
    through the same page-table indirection as kpos, so radix-shared
    prefix pages dequantize identically for every lane that names them.
    Dequantization happens inside the kernel (VMEM) / oracle (f32), and
    the result is cast back to q.dtype here so both impls return the same
    dtype the unquantized path would.
    """
    impl = impl or default_impl()
    assert k.dtype == jnp.int8 and v.dtype == jnp.int8, (k.dtype, v.dtype)
    if mesh is not None and axis is not None and mesh.shape[axis] > 1:
        b = q.shape[0]
        act = (jnp.ones((b,), bool) if active is None
               else active.astype(bool))

        def call(qb, kb, vb, ksb, vsb, kpb, ptb, qpb, ab):
            return paged_flash_decode_q(qb, kb, vb, ksb, vsb, kpb, ptb,
                                        qpb, active=ab, impl=impl)

        # scale planes (P, ps, KVH) ride the same kv-head partition as the
        # int8 values they dequantize
        return _shard_map_heads(call, mesh, axis, q,
                                (k, v, k_scale, v_scale),
                                (kpos, page_table, qpos, act))
    if impl == "ref":
        out = _ref.paged_flash_decode_q(q, k, v, k_scale, v_scale, kpos,
                                        page_table, qpos, active=active)
        return out.astype(q.dtype)
    from repro.kernels import flash_decode as _fd

    b, h, hd = q.shape
    kvh = k.shape[2]
    g = h // kvh
    qg = q.reshape(b, kvh, g, hd)
    gp = _rup(g, 8)  # group dim is the sublane axis: pad to tile granularity
    if gp != g:
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, gp - g), (0, 0)))
    act = (jnp.ones((b,), jnp.int32) if active is None
           else active.astype(jnp.int32).reshape(b))
    out = _fd.paged_flash_decode_q(
        qg, k, v, k_scale.astype(jnp.float32), v_scale.astype(jnp.float32),
        kpos.astype(jnp.int32), page_table.astype(jnp.int32),
        qpos.astype(jnp.int32), act, interpret=impl == "interpret")
    return out[:, :, :g].reshape(b, h, hd)


def i_layernorm(q8: jax.Array, prep: LNParams, impl: Optional[str] = None):
    """Integer LayerNorm over last axis. Returns (int32 values, s_out)."""
    impl = impl or default_impl()
    if impl == "ref":
        out = _ref.i_layernorm_rows(q8, prep.q_gamma, prep.q_beta, prep.s_gamma)
        return out, prep.s_out
    shape = q8.shape
    q2 = q8.reshape(-1, shape[-1])
    rows = q2.shape[0]
    br = min(_iln.BLOCK_ROWS, rows)
    q2 = _pad_to(q2, br, 0)
    out = _iln.i_layernorm(q2, prep.q_gamma, prep.q_beta, block_rows=br,
                           interpret=impl == "interpret")
    return out[:rows].reshape(shape), prep.s_out
