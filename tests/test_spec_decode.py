"""Speculative decoding: lossless greedy verification.

Correctness bar, per ISSUE 6: speculative token streams are BIT-IDENTICAL
to `Model.decode_steps` streams for every speculation depth, acceptance
rate, and KV numeric — acceptance only moves *throughput*, never a token.
Plus the acceptance state machine's unit semantics and the KV manager's
dual-arena (draft + target) page accounting.
"""
import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.models.transformer import init_params, make_model, spec_acceptance
from repro.serving.kv_manager import KVManager, kv_page_bytes, spec_pool_split


@pytest.fixture
def ref_impl():
    """Pin the kernel impl to the jnp oracle: spec-vs-sequential equality
    needs prefill/decode/verify to share one summation order (the reduced
    configs' bf16 logits carry exact top-2 ties)."""
    from repro.kernels import ops
    prev = ops._IMPL
    ops.set_impl("ref")
    yield
    ops._IMPL = prev


# ---------------------------------------------------------------------------
# spec_acceptance unit semantics
# ---------------------------------------------------------------------------


def _accept(ins, tgt, active=None, rem=None, eos=None, forced=None,
            flen=None, fptr=None, pad=0):
    ins = jnp.asarray(ins, jnp.int32)
    tgt = jnp.asarray(tgt, jnp.int32)
    b = ins.shape[0]
    active = (jnp.ones((b,), bool) if active is None
              else jnp.asarray(active, bool))
    rem = (jnp.full((b,), 100, jnp.int32) if rem is None
           else jnp.asarray(rem, jnp.int32))
    eos = (jnp.full((b,), -1, jnp.int32) if eos is None
           else jnp.asarray(eos, jnp.int32))
    forced = (jnp.zeros((b, 1), jnp.int32) if forced is None
              else jnp.asarray(forced, jnp.int32))
    flen = (jnp.zeros((b,), jnp.int32) if flen is None
            else jnp.asarray(flen, jnp.int32))
    fptr = (jnp.zeros((b,), jnp.int32) if fptr is None
            else jnp.asarray(fptr, jnp.int32))
    out = spec_acceptance(ins, tgt, active, rem, eos, pad, forced, flen,
                          fptr)
    return [np.asarray(x) for x in out]


def test_acceptance_full_and_divergent():
    # lane 0: drafted inputs all match the target's argmaxes -> every
    # position emits and the bonus token (tgt[-1]) becomes the next input;
    # lane 1: ins[2] != tgt[1] -> steps 0-1 emit, step 2 is a hole, and
    # the correction token tgt[1] (already emitted) becomes the next input
    emit, cur, alive, rem, fptr, v = _accept(
        ins=[[5, 10, 11], [5, 20, 99]],
        tgt=[[10, 11, 12], [20, 21, 22]])
    assert emit.T.tolist() == [[10, 11, 12], [20, 21, -1]]
    assert cur.tolist() == [12, 21]
    assert v.tolist() == [3, 2]
    assert alive.tolist() == [True, True]
    assert rem.tolist() == [97, 98]


def test_acceptance_eos_and_budget_exit():
    # lane 0 emits its EOS at step 1 -> step 2 is a hole, lane dead, pad
    # fed; lane 1 has budget 1 -> emits once then exits
    emit, cur, alive, rem, fptr, v = _accept(
        ins=[[5, 10, 7], [5, 20, 21]],
        tgt=[[10, 7, 12], [20, 21, 22]],
        rem=[100, 1], eos=[7, -1], pad=0)
    assert emit.T.tolist() == [[10, 7, -1], [20, -1, -1]]
    assert alive.tolist() == [False, False]
    assert cur.tolist() == [0, 0]
    # the dead lanes consumed exactly the steps that ran
    assert v.tolist() == [2, 1]


def test_acceptance_forced_queue_swallows_then_emits():
    # forced queue covers 2 pending positions: steps 0-1 are swallowed
    # prompt ingest (emit -1, budget untouched), step 2 emits the first
    # generated token; forced inputs are always "matched" (not drafted)
    emit, cur, alive, rem, fptr, v = _accept(
        ins=[[5, 8, 9]], tgt=[[50, 51, 52]],
        forced=[[8, 9, 0, 0]], flen=[2], fptr=[0], rem=[10])
    assert emit.T.tolist() == [[-1, -1, 52]]
    assert v.tolist() == [3]
    assert fptr.tolist() == [2]
    assert rem.tolist() == [9]
    assert cur.tolist() == [52]


def test_acceptance_inactive_lane_untouched():
    emit, cur, alive, rem, fptr, v = _accept(
        ins=[[5, 6, 7]], tgt=[[1, 2, 3]], active=[False], pad=0)
    assert emit.T.tolist() == [[-1, -1, -1]]
    assert v.tolist() == [0]
    assert not alive[0] and cur[0] == 0


# ---------------------------------------------------------------------------
# model-level bit-identity property: spec == decode_steps
# ---------------------------------------------------------------------------


def _spec_setup(draft_kind):
    cfg = get_config("smollm-135m").reduced()
    model = make_model(cfg, remat=False)
    params = init_params(cfg, jax.random.PRNGKey(0))
    if draft_kind == "self":
        # the target drafting for itself: acceptance is exactly 1.0, the
        # full-acceptance + bonus-token path every dispatch
        return cfg, model, params, model, params
    dcfg = dataclasses.replace(cfg, name=cfg.name + "-draft", n_layers=1)
    draft = make_model(dcfg, remat=False)
    # random init: near-zero acceptance, the all-rejected fallback path
    dparams = init_params(dcfg, jax.random.PRNGKey(1))
    return cfg, model, params, draft, dparams


def _run_spec_vs_sequential(model, params, draft, dparams, k, kv_dtype,
                            budgets=(9, 6)):
    """Drive spec_decode_step to drain and compare against decode_steps,
    ingesting a 3-token prompt through the forced queue on both paths."""
    b, ps, maxp = 2, 4, 16
    num_pages = b * 8 + 1
    prompt = np.array([[5, 7, 9], [11, 3, 2]], np.int32)
    pt = np.zeros((b, maxp), np.int32)
    pt[0, :8] = np.arange(1, 9)
    pt[1, :8] = np.arange(9, 17)

    def fresh(m):
        c = m.init_paged_cache(b, num_pages, ps, maxp, kv_dtype)
        return dict(c, pt=jnp.asarray(pt))

    forced = np.zeros((b, 32), np.int32)
    forced[:, :2] = prompt[:, 1:]
    forced = jnp.asarray(forced)
    flen = jnp.full((b,), 2, jnp.int32)
    token = jnp.asarray(prompt[:, 0])
    active = jnp.ones((b,), bool)
    eos = jnp.full((b,), -1, jnp.int32)
    budget = jnp.asarray(budgets, jnp.int32)

    toks_ref, *_ = model.decode_steps(
        params, fresh(model), token, active, 16, eos_id=eos, budget=budget,
        forced=forced, forced_len=flen, forced_ptr=jnp.zeros((b,), jnp.int32))
    toks_ref = np.asarray(toks_ref)
    ref = [[int(t) for t in toks_ref[:, lane] if t >= 0] for lane in range(b)]

    st_c, st_d = fresh(model), fresh(draft)
    cur, act, rem = token, active, budget
    fptr = jnp.zeros((b,), jnp.int32)
    out = [[] for _ in range(b)]
    for _ in range(24):
        toks, cur, act, rem, fptr, st_c, st_d, _ = model.spec_decode_step(
            params, st_c, cur, act, k, draft, dparams, st_d, eos_id=eos,
            budget=rem, forced=forced, forced_len=flen, forced_ptr=fptr)
        tb = np.asarray(toks)
        for lane in range(b):
            out[lane].extend(int(t) for t in tb[:, lane] if t >= 0)
        if not bool(np.asarray(act).any()):
            break
    assert out == ref, (out, ref)


# trimmed cross-product (each case compiles its own spec programs and
# costs ~1-2 min on CPU): every k in {1,2,4,8}, both draft kinds and both
# arena dtypes appear, with int8 paired against the cases bf16 skips
@pytest.mark.parametrize("k,draft_kind,kv_dtype", [
    (1, "random", "bf16"), (8, "random", "bf16"), (4, "self", "bf16"),
    (2, "random", "int8"), (4, "self", "int8"),
])
def test_spec_stream_bit_identical(k, draft_kind, kv_dtype, ref_impl):
    cfg, model, params, draft, dparams = _spec_setup(draft_kind)
    _run_spec_vs_sequential(model, params, draft, dparams, k, kv_dtype)


def test_spec_stream_bit_identical_mid_acceptance(ref_impl):
    """Fitted draft/target pair with the disagreement knob: the draft
    trains on a corpus deviated at every value ≡ 0 (mod 2), so it agrees
    with the clean-fitted target on only part of the greedy steps —
    exercising partial-acceptance blocks (neither all-accept nor
    all-reject), which must still be bit-identical."""
    from repro.models.synthetic import fit_affine_lm

    cfg = get_config("smollm-135m").reduced()
    model = make_model(cfg, remat=False)
    params = fit_affine_lm(model, steps=300)
    dcfg = dataclasses.replace(cfg, name=cfg.name + "-draft", n_layers=1)
    draft = make_model(dcfg, remat=False)
    dparams = fit_affine_lm(draft, steps=300, disagree_every=2)
    _run_spec_vs_sequential(model, params, draft, dparams, 4, "bf16",
                            budgets=(12, 9))


def test_verify_fold_rows_bitwise_equal_single_rows(monkeypatch):
    """The verify pass folds B x T query rows into the paged kernel's
    batch the way models/attention.py does: each lane's page-table row
    repeated T times, positions p..p+T-1.  Each folded row is bitwise the
    single-row call at its own position, whichever blocks of `ppb` pages
    its own position makes it walk (lane 0's rows cross the 12-key block
    boundary, lane 2 is inactive).  On the CPU the engine routes to the
    jnp oracle, so only a direct call reaches the kernel."""
    from repro.kernels import flash_decode as fd, ops
    from repro.models.attention import fuse_kv

    b, t, h, kvh, hd, ps, maxp, ppb = 3, 4, 4, 2, 16, 4, 8, 3
    monkeypatch.setattr(fd, "PAGED_BLOCK_BYTES", ppb * 2 * ps * kvh * hd * 2)
    assert fd.pages_per_block(ps, 2 * kvh * hd * 2, maxp) == ppb
    rng = np.random.default_rng(0)
    n_pages = 1 + b * maxp
    k = jnp.asarray(rng.normal(0, 1, (n_pages, ps, kvh, hd)), jnp.bfloat16)
    v = jnp.asarray(rng.normal(0, 1, (n_pages, ps, kvh, hd)), jnp.bfloat16)
    pt = np.arange(1, n_pages, dtype=np.int32).reshape(b, maxp)
    p0 = np.array([10, 25, 3])
    kpos = np.full((n_pages, ps), 2 ** 30, np.int32)
    for lane in range(b):  # written up to the block's last row
        for pos in range(p0[lane] + t):
            kpos[pt[lane, pos // ps], pos % ps] = pos
    q = jnp.asarray(rng.normal(0, 0.25, (b, t, h, hd)), jnp.bfloat16)
    qf = q.reshape(b * t, h, hd)
    ptf = jnp.repeat(jnp.asarray(pt), t, axis=0)
    qpf = jnp.asarray((p0[:, None] + np.arange(t)).reshape(-1), jnp.int32)
    actf = jnp.repeat(jnp.asarray([True, True, False]), t)
    kpos = jnp.asarray(kpos)
    kv = fuse_kv(k, v)
    folded = np.asarray(ops.paged_flash_decode(
        qf, kv, kpos, ptf, qpf, active=actf, impl="interpret"))
    for row in range(b * t):
        one = ops.paged_flash_decode(
            qf[row:row + 1], kv, kpos, ptf[row:row + 1],
            qpf[row:row + 1], active=actf[row:row + 1], impl="interpret")
        np.testing.assert_array_equal(folded[row], np.asarray(one)[0])
    assert not folded[2 * t:].any()  # the inactive lane's rows
    assert folded[:2 * t].any()


# ---------------------------------------------------------------------------
# dual-arena page accounting
# ---------------------------------------------------------------------------


def test_kv_manager_draft_arena_alloc_release_drain():
    kv = KVManager(num_pages=16, page_size=4, max_batch=2, max_pages=8,
                   draft_num_pages=8)
    g = kv.admit(np.arange(6, dtype=np.int32), rem_budget=6,
                 max_hit_suffix=8, spec_margin=4)
    assert g is not None
    # 6 prompt + 6 budget + 4 spec margin = 16 positions = 4 pages per arena
    assert len(g.pages) == 4 and len(g.draft_pages) == 4
    assert g.draft_pt_row[:4].tolist() == g.draft_pages
    # draft pages are always freshly owned: the whole span resets
    assert g.draft_reset[:4].tolist() == g.draft_pages
    kv.commit(0, g)
    assert kv.draft_pool.pages_in_use == 4
    # release covers retirement, preemption, and rejection rollback alike —
    # draft pages are never shared, so all three reduce to a lane decref
    kv.release(0)
    assert kv.draft_pool.pages_in_use == 0
    kv.assert_drained()


def test_kv_manager_draft_starvation_rolls_back_admission():
    """When the draft pool can't cover an admission, the whole admission
    declines as a unit: target-side hit refs taken by the radix lookup are
    dropped and nothing leaks."""
    kv = KVManager(num_pages=64, page_size=4, max_batch=4, max_pages=16,
                   draft_num_pages=6)
    prompt = np.arange(8, dtype=np.int32)
    g = kv.admit(prompt, rem_budget=8, max_hit_suffix=16, spec_margin=0)
    assert g is not None and len(g.draft_pages) == 4
    kv.commit(0, g)
    kv.register_prefix(prompt, g.pages)
    in_use_before = kv.pool.pages_in_use
    # second admission hits the radix prefix but needs 4 draft pages with
    # only 2 free -> must decline and roll the hit incref back
    g2 = kv.admit(prompt, rem_budget=8, max_hit_suffix=16, spec_margin=0)
    assert g2 is None
    assert kv.pool.pages_in_use == in_use_before
    assert kv.draft_pool.pages_in_use == 4
    kv.release(0)
    kv.assert_drained()


def test_spec_pool_split_partitions_budget():
    cfg = get_config("smollm-135m").reduced()
    dcfg = dataclasses.replace(cfg, name=cfg.name + "-draft", n_layers=1)
    ps = 16
    budget = 64 * kv_page_bytes(cfg, ps, "bf16")
    n = spec_pool_split(cfg, dcfg, ps, "bf16", budget)
    # per-arena page count: both arenas hold n pages within the budget...
    assert n * (kv_page_bytes(cfg, ps, "bf16")
                + kv_page_bytes(dcfg, ps, "bf16")) <= budget
    # ...and one more page per arena would overflow it
    assert (n + 1) * (kv_page_bytes(cfg, ps, "bf16")
                      + kv_page_bytes(dcfg, ps, "bf16")) > budget
    # the 1-layer draft is cheaper per page, so the split beats halving
    assert n > 64 // 4
