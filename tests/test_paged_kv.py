"""Paged KV: allocator/radix-tree invariants and kernel/oracle equality.

Kernel bar: for random page tables — including pages *shared between
lanes* (radix prefix reuse) — the paged Pallas kernel (interpret mode,
real body), the paged jnp oracle, and the dense split-KV path over the
explicitly gathered cache all agree; on page-aligned logical lengths the
paged oracle is *bitwise* identical to the dense oracle, which is the
property the serving engine's stream-equality guarantees stand on.

Pool bar: pages never leak — refcounts across lanes and the radix tree
reconcile to zero when everything releases, eviction only frees
tree-exclusive pages, and lookups never hand out a prompt's final token.
"""
import numpy as np
import pytest

import jax.numpy as jnp

try:
    from hypothesis import given, settings, strategies as st
except ModuleNotFoundError:  # deterministic fallback draws (see detshim.py)
    from detshim import given, settings
    import detshim as st

from repro.core.packing import PagePool, RadixPrefixCache
from repro.kernels import flash_decode as fd
from repro.kernels import ops

SENTINEL = 2 ** 30


# ---------------------------------------------------------------------------
# paged flash-decode vs oracles
# ---------------------------------------------------------------------------


def _mk_paged(rng, b, h, kvh, hd, n_pages, ps, maxp, share=True,
              dtype=jnp.float32):
    """Random arena + per-lane tables; lanes may share table entries."""
    q = jnp.asarray(rng.normal(0, 1, (b, h, hd)), dtype) * (hd ** -0.5)
    k = jnp.asarray(rng.normal(0, 1, (n_pages, ps, kvh, hd)), dtype)
    v = jnp.asarray(rng.normal(0, 1, (n_pages, ps, kvh, hd)), dtype)
    # page j of any lane holds positions [j*ps, (j+1)*ps): kpos per arena
    # page is consistent for every logical depth it may appear at only if
    # tables agree on depth — build depth-consistent tables like the
    # engine's allocator does (a shared page is a shared *prefix* page)
    kpos = np.full((n_pages, ps), SENTINEL, np.int64)
    pt = np.zeros((b, maxp), np.int32)
    next_page = 1  # page 0 = trash (all sentinel)
    shared = {}
    for lane in range(b):
        for j in range(maxp):
            if share and j in shared and rng.random() < 0.5:
                pt[lane, j] = shared[j]  # prefix page shared across lanes
            else:
                page = next_page
                next_page += 1
                assert page < n_pages
                pt[lane, j] = page
                shared.setdefault(j, page)
                kpos[page] = j * ps + np.arange(ps)
    qpos = jnp.asarray(rng.integers(ps, maxp * ps, b), jnp.int32)
    return (q, k, v, jnp.asarray(kpos, jnp.int32), jnp.asarray(pt), qpos)


def _with_ppb(monkeypatch, ppb, ps, kvh, hd, maxp, itemsize=4):
    """Steer the wrapper's pages-per-block to `ppb` through the block
    byte budget it derives ppb from."""
    monkeypatch.setattr(fd, "PAGED_BLOCK_BYTES",
                        ppb * 2 * ps * kvh * hd * itemsize)
    assert fd.pages_per_block(ps, kvh, hd, itemsize, maxp) == ppb


# name: (heads, kvh, ps, maxp, ppb, qpos per lane, lane active)
WALKS = {
    # one block holds the whole table: the walk of the first kernel
    "one_block": (4, 4, 8, 3, 3, [20, 9, 17], [1, 1, 1]),
    # contexts that end just before, on and just after a block boundary
    # (ppb * ps = 16 keys)
    "cross_block_boundary": (8, 2, 8, 6, 2, [15, 16, 17, 31, 32, 47],
                             [1] * 6),
    # ppb does not divide MAXP: the last block is short of the table
    "ppb_not_dividing_maxp": (6, 3, 8, 7, 3, [55, 40, 23, 47], [1] * 4),
    # lanes with 0 (inactive), 1 and every page live, and a query past
    # the table's reach
    "zero_one_all_pages_live": (4, 2, 8, 5, 2, [30, 3, 39, 7, 500],
                                [0, 1, 1, 1, 1]),
    # inactive lanes between active ones
    "inactive_between_active": (4, 4, 16, 4, 3, [40, 60, 9, 33, 63, 0],
                                [1, 0, 0, 1, 0, 1]),
}


@pytest.mark.parametrize("seed", [0, 1])
@pytest.mark.parametrize("walk", sorted(WALKS))
def test_paged_decode_interpret_matches_ref(walk, seed, monkeypatch):
    """Pallas paged kernel (interpret) == gather oracle, radix-shared
    pages included, over walks of several blocks of `ppb` pages."""
    h, kvh, ps, maxp, ppb, qpos, active = WALKS[walk]
    rng = np.random.default_rng(seed)
    b, hd = len(qpos), 16
    n_pages = 1 + b * maxp + 1
    q, k, v, kpos, pt, _ = _mk_paged(rng, b, h, kvh, hd, n_pages, ps, maxp)
    qpos = jnp.asarray(qpos, jnp.int32)
    active = jnp.asarray(active, bool)
    _with_ppb(monkeypatch, ppb, ps, kvh, hd, maxp)
    got = ops.paged_flash_decode(q, k, v, kpos, pt, qpos, active=active,
                                 impl="interpret")
    want = ops.paged_flash_decode(q, k, v, kpos, pt, qpos, active=active,
                                  impl="ref")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


def test_paged_decode_skips_pages_past_the_bound(monkeypatch):
    """K/V of every page past a lane's live pages are NaN (kpos sentinel),
    the trash page's too: the walk never folds them in, so the output
    stays finite and equals the oracle over a clean arena.  A kernel that
    walked them would leak NaN through 0 * NaN in p @ v."""
    rng = np.random.default_rng(3)
    b, h, kvh, hd, ps, maxp, ppb = 4, 4, 2, 16, 8, 6, 2
    n_pages = 1 + b * maxp
    q, k, v, kpos, pt, _ = _mk_paged(rng, b, h, kvh, hd, n_pages, ps, maxp,
                                     share=False)
    qpos = np.asarray([5, 20, 47, 33], np.int32)  # 1, 3, 6 and 5 pages
    dead = [0] + [int(pt[i, j]) for i in range(b) for j in range(maxp)
                  if j > qpos[i] // ps]
    kpos = kpos.at[np.asarray(dead)].set(SENTINEL)
    k_nan = k.at[np.asarray(dead)].set(jnp.nan)
    v_nan = v.at[np.asarray(dead)].set(jnp.nan)
    qpos = jnp.asarray(qpos)
    _with_ppb(monkeypatch, ppb, ps, kvh, hd, maxp)
    got = np.asarray(ops.paged_flash_decode(q, k_nan, v_nan, kpos, pt, qpos,
                                            impl="interpret"))
    want = ops.paged_flash_decode(q, k, v, kpos, pt, qpos, impl="ref")
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, np.asarray(want), atol=2e-5, rtol=2e-5)


LATENT_R, LATENT_DV = 24, 16  # a latent row (kv rank + rope) and its values


def _with_latent_ppb(monkeypatch, ppb, ps, maxp, itemsize=4):
    """As _with_ppb, for the latent arena's one row a key."""
    monkeypatch.setattr(fd, "PAGED_BLOCK_BYTES",
                        ppb * ps * LATENT_R * itemsize)
    assert fd.pages_per_block(ps, 1, LATENT_R, itemsize, maxp,
                              arenas=1) == ppb


@pytest.mark.parametrize("stacked", [False, True],
                         ids=["one_layer", "stacked_layers"])
@pytest.mark.parametrize("seed", [0, 1])
@pytest.mark.parametrize("walk", sorted(WALKS))
def test_paged_decode_latent_interpret_matches_ref(walk, seed, stacked,
                                                   monkeypatch):
    """The kernel's latent mode (MLA absorbed decode): one (P, ps, R)
    arena, every query head in one group, each key's values the first dv
    columns of its own row; interpret == oracle over the same walks.
    Stacked: three layers' arenas (L, P, ps, R) and kpos (L, P, ps),
    the kernel told layer 1, against the oracle over layer 1 alone."""
    h, _, ps, maxp, ppb, qpos, active = WALKS[walk]
    rng = np.random.default_rng(seed)
    b = len(qpos)
    n_pages = 1 + b * maxp + 1
    q, c, _, kpos, pt, _ = _mk_paged(rng, b, h, 1, LATENT_R, n_pages, ps,
                                     maxp)
    c = c[:, :, 0]  # (P, ps, R): one row a key
    qpos = jnp.asarray(qpos, jnp.int32)
    active = jnp.asarray(active, bool)
    _with_latent_ppb(monkeypatch, ppb, ps, maxp)
    want = ops.paged_flash_decode_latent(q, c, kpos, pt, qpos, LATENT_DV,
                                         active=active, impl="ref")
    layer = None
    if stacked:  # layers 0 and 2 hold other rows and other positions
        other = jnp.asarray(rng.normal(0, 1, c.shape), c.dtype)
        c = jnp.stack([other, c, -other])
        kpos = jnp.stack([kpos[::-1], kpos, kpos + 1])
        layer = jnp.int32(1)
    got = ops.paged_flash_decode_latent(q, c, kpos, pt, qpos, LATENT_DV,
                                        active=active, layer=layer,
                                        impl="interpret")
    assert got.shape == (b, h, LATENT_DV)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


def _pallas_operands(fn, *args):
    """Operand avals of the pallas_call(s) `fn` traces to."""
    import jax
    out = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                out.append([v.aval for v in eqn.invars])
            for p in eqn.params.values():
                if hasattr(p, "jaxpr") and hasattr(p.jaxpr, "eqns"):
                    walk(p.jaxpr)
                elif hasattr(p, "eqns"):
                    walk(p)
    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return out


def test_paged_decode_latent_one_dma_a_page_and_nan_past_the_bound(
        monkeypatch):
    """Rows of every page past a lane's live pages are NaN (kpos
    sentinel): the latent walk never folds them in, so the output stays
    finite and equals the oracle over a clean arena.  And the arena is
    one operand a block slot (ppb in all), not two as for K and V: each
    page is loaded once and its values sliced from it."""
    rng = np.random.default_rng(3)
    b, h, ps, maxp, ppb = 4, 16, 8, 6, 2
    q, c, _, kpos, pt, _ = _mk_paged(rng, b, h, 1, LATENT_R, 1 + b * maxp,
                                     ps, maxp, share=False)
    c = c[:, :, 0]  # (P, ps, R): one row a key
    qpos = np.asarray([5, 20, 47, 33], np.int32)
    dead = [0] + [int(pt[i, j]) for i in range(b) for j in range(maxp)
                  if j > qpos[i] // ps]
    kpos = kpos.at[np.asarray(dead)].set(SENTINEL)
    c_nan = c.at[np.asarray(dead)].set(jnp.nan)
    qpos = jnp.asarray(qpos)
    _with_latent_ppb(monkeypatch, ppb, ps, maxp)

    def call(cc):
        return ops.paged_flash_decode_latent(q, cc, kpos, pt, qpos,
                                             LATENT_DV, impl="interpret")
    got = np.asarray(call(c_nan))
    want = ops.paged_flash_decode_latent(q, c, kpos, pt, qpos, LATENT_DV,
                                         impl="ref")
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, np.asarray(want), atol=2e-5, rtol=2e-5)
    (avals,) = _pallas_operands(call, c_nan)
    assert sum(a.shape == c.shape for a in avals) == ppb


@given(st.integers(0, 10_000))
@settings(max_examples=8, deadline=None)
def test_paged_ref_bitwise_equals_dense_ref(seed):
    """Gathering the pages into a dense per-lane cache and running the
    dense oracle is *bitwise* what the paged oracle computes — the
    foundation of paged-vs-dense engine stream equality."""
    rng = np.random.default_rng(seed)
    b, h, kvh, hd, ps, maxp = 2, 4, 2, 16, 8, 4
    n_pages = 1 + b * maxp
    q, k, v, kpos, pt, qpos = _mk_paged(rng, b, h, kvh, hd, n_pages, ps,
                                        maxp)
    paged = ops.paged_flash_decode(q, k, v, kpos, pt, qpos, impl="ref")
    kg = jnp.asarray(np.asarray(k)[np.asarray(pt)].reshape(b, -1, kvh, hd))
    vg = jnp.asarray(np.asarray(v)[np.asarray(pt)].reshape(b, -1, kvh, hd))
    kpg = jnp.asarray(np.asarray(kpos)[np.asarray(pt)].reshape(b, -1))
    dense = ops.flash_decode(q, kg, vg, kpg, qpos, impl="ref")
    np.testing.assert_array_equal(np.asarray(paged), np.asarray(dense))


def test_paged_decode_inactive_and_sentinel_rows():
    """Inactive lanes and all-sentinel (never written / trash) pages give
    exact zeros, never NaN, in both impls."""
    rng = np.random.default_rng(7)
    b, h, kvh, hd, ps, maxp = 3, 4, 2, 16, 8, 3
    q, k, v, kpos, pt, qpos = _mk_paged(rng, b, h, kvh, hd, 1 + b * maxp,
                                        ps, maxp)
    pt = pt.at[2].set(0)  # lane 2's whole table points at the trash page
    active = jnp.asarray([True, False, True])
    for impl in ("ref", "interpret"):
        out = np.asarray(ops.paged_flash_decode(q, k, v, kpos, pt, qpos,
                                                active=active, impl=impl))
        assert not np.isnan(out).any(), impl
        np.testing.assert_array_equal(out[1], 0.0)  # inactive
        np.testing.assert_array_equal(out[2], 0.0)  # all-sentinel pages


def test_paged_decode_trash_page_garbage_is_unreachable():
    """Garbage k/v in the trash page (inactive lanes scatter there) must
    not perturb live lanes as long as its kpos stay sentinel."""
    rng = np.random.default_rng(11)
    b, h, kvh, hd, ps, maxp = 2, 4, 2, 16, 8, 3
    q, k, v, kpos, pt, qpos = _mk_paged(rng, b, h, kvh, hd, 1 + b * maxp,
                                        ps, maxp)
    clean = ops.paged_flash_decode(q, k, v, kpos, pt, qpos, impl="ref")
    k2 = k.at[0].set(1e9)
    v2 = v.at[0].set(-1e9)
    dirty = ops.paged_flash_decode(q, k2, v2, kpos, pt, qpos, impl="ref")
    np.testing.assert_array_equal(np.asarray(clean), np.asarray(dirty))


# ---------------------------------------------------------------------------
# PagePool / RadixPrefixCache
# ---------------------------------------------------------------------------


def test_page_pool_alloc_refcount_free():
    pool = PagePool(num_pages=8, page_size=4)
    assert pool.free_pages == 7  # page 0 reserved (trash)
    a = pool.alloc(3)
    assert pool.pages_in_use == 3 and 0 not in a
    pool.incref(a)
    assert pool.decref(a) == []          # still referenced once
    assert sorted(pool.decref(a)) == sorted(a)  # now free
    assert pool.pages_in_use == 0
    with pytest.raises(MemoryError):
        pool.alloc(8)
    assert pool.pages_for(1) == 1 and pool.pages_for(9) == 3


def test_radix_lookup_caps_at_prompt_minus_one():
    """A full-prompt hit would leave nothing to run the first forward pass
    on; the final token is never handed out."""
    pool = PagePool(num_pages=8, page_size=4)
    rc = RadixPrefixCache(pool)
    prompt = np.arange(8, dtype=np.int32)
    pages = pool.alloc(2)
    rc.insert(prompt, pages)
    hit, hlen = rc.lookup(prompt)  # identical prompt
    assert hlen == 4 and hit == pages[:1]
    pool.decref(hit)
    pool.decref(pages)


def test_radix_shared_prefix_hit_and_eviction():
    pool = PagePool(num_pages=12, page_size=4)
    rc = RadixPrefixCache(pool)
    prompt_a = np.concatenate([np.arange(8), [50, 51]]).astype(np.int32)
    pages_a = pool.alloc(3)
    rc.insert(prompt_a, pages_a)  # registers the 2 full pages
    assert rc.cached_pages == 2
    prompt_b = np.concatenate([np.arange(8), [60, 61, 62]]).astype(np.int32)
    hit, hlen = rc.lookup(prompt_b)
    assert hlen == 8 and hit == pages_a[:2]
    # a page held by a "lane" (the lookup ref) is not evictable
    assert rc.evict(10) == 0
    pool.decref(hit)
    pool.decref(pages_a)
    # now only tree refs remain: eviction frees exactly the cached pages
    assert rc.evict(10) == 2
    assert pool.pages_in_use == 0 and rc.cached_pages == 0


def test_radix_insert_only_full_pages():
    pool = PagePool(num_pages=8, page_size=4)
    rc = RadixPrefixCache(pool)
    prompt = np.arange(7, dtype=np.int32)  # one full page + a partial
    pages = pool.alloc(2)
    assert rc.insert(prompt, pages) == 1   # the partial page is private
    assert rc.cached_pages == 1
    pool.decref(pages)


def test_radix_lru_eviction_order():
    pool = PagePool(num_pages=8, page_size=2)
    rc = RadixPrefixCache(pool)
    old = pool.alloc(1)
    new = pool.alloc(1)
    rc.insert(np.asarray([1, 2], np.int32), old)
    rc.insert(np.asarray([3, 4], np.int32), new)
    pool.decref(old)
    pool.decref(new)
    hit, _ = rc.lookup(np.asarray([3, 4, 9], np.int32))  # refresh `new`
    pool.decref(hit)
    freed = rc.evict(1)
    assert freed == 1
    # the untouched (LRU) entry went first
    assert pool.refcount(old[0]) == 0 and pool.refcount(new[0]) == 1
