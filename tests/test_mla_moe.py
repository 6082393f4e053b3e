"""Latent attention, the leading dense layer and the expert share, against
the plain float32 reference (tests/deepseek_v3_ref.py) on seeded random
weights at a CPU size.

Logits, not tokens, are compared: with random weights the largest logit
changes on rounding.  The program runs bfloat16 activations and weights
(f32 norms, router and accumulations); the reference runs float32 at
HIGHEST.  Tolerances are on the largest logit error over the logits'
spread (max |logit|):

- TOL_LOGITS 0.05: a bfloat16 activation carries ~2^-9 relative error;
  a few dozen roundings a layer over 3 layers read 1.7% on a 20-token
  forward here.  Patched into a copy of the program, a dropped rope on
  the shared key read 68%, a missing kv_a RMSNorm 37% and top-k weights
  left unnormalized 33%;
- TOL_LAYER 0.02 for one MoE layer: 0.7% here, 34% with the top-k
  weights unnormalized.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepseek_v3_ref as ref
from repro.configs.base import ModelConfig
from repro.models import moe as moe_mod
from repro.models.transformer import (
    _layers, init_params, layer_plan, make_model,
)
from repro.serving.engine import ContinuousBatchingEngine
from repro.serving.kv_manager import kv_page_bytes
from repro.serving.scheduler import Request

TOL_LOGITS = 0.05
TOL_LAYER = 0.02

CFG = ModelConfig(
    name="mla-tiny", family="moe", n_layers=3, d_model=64, n_heads=4,
    n_kv_heads=1, d_ff=32, vocab_size=256, kv_lora_rank=32,
    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
    rope_theta=50_000.0, n_experts=8, top_k=3, n_shared_experts=2,
    routed_scale=2.446, experts_held=4, expert_offset=2,
    first_k_dense=1, dense_d_ff=96, max_seq_len=256)
PS = 8  # page size: contexts below cross page boundaries


def _params(cfg=CFG, seed=0):
    params = init_params(cfg, jax.random.PRNGKey(seed))
    # a selection bias that matters: about the spread of the scores
    bias = 0.3 * jax.random.normal(jax.random.PRNGKey(seed + 1),
                                   (cfg.n_experts,))
    params["scan"]["b0"]["ffn"]["router_bias"] = jnp.broadcast_to(
        bias, params["scan"]["b0"]["ffn"]["router_bias"].shape)
    return params


def _rel(got, want):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return float(np.abs(got - want).max() / np.abs(want).max())


# -- (a) prefill, then paged decode, against the full forward ------------------

def test_prefill_then_paged_decode_match_reference():
    """Lane 0: a 13-token prompt prefilled into a 16-token bucket and
    scattered into pages, then 10 decode steps across the page boundary
    at 16.  Lane 1: a radix prefix hit on lane 0's first page (8 tokens),
    its suffix fed one token a step through the same decode, then decoded
    on.  Every step's logits against the reference's full forward of the
    lane's sequence."""
    cfg, params = CFG, _params()
    model = make_model(cfg, remat=False)
    rng = np.random.default_rng(0)
    seq0 = rng.integers(0, cfg.vocab_size, 23).astype(np.int32)
    seq1 = np.concatenate([seq0[:8], rng.integers(0, cfg.vocab_size, 15)
                           ]).astype(np.int32)
    n0 = 13
    bucket = 16
    toks = np.zeros((1, bucket), np.int32)
    toks[0, :n0] = seq0[:n0]
    pos = np.full((1, bucket), 2 ** 30, np.int32)
    pos[0, :n0] = np.arange(n0)
    logits, small = model.prefill(params, model.init_cache(1, bucket),
                                  tokens=jnp.asarray(toks),
                                  positions=jnp.asarray(pos),
                                  last_idx=jnp.asarray([n0 - 1]))
    want0 = ref.forward(cfg, params, seq0)
    want1 = ref.forward(cfg, params, seq1)
    errs = [_rel(logits[0], want0[n0 - 1])]

    maxp = 4
    big = model.init_paged_cache(2, 1 + 2 * maxp, PS, maxp)
    pt0 = np.asarray([1, 2, 3, 4], np.int32)
    big = model.admit_lane_cache(big, 0, pt0, n0, pt0, small=small,
                                 write_pages=np.asarray([1, 2], np.int32))
    pt1 = np.asarray([1, 5, 6, 7], np.int32)  # page 1 shared (the hit)
    big = model.admit_lane_cache(big, 1, pt1, 8, np.asarray([5, 6, 7, 0],
                                                            np.int32))
    act = jnp.asarray([True, True])
    for step in range(10):
        t0, t1 = n0 + step, 8 + step  # each lane's input position
        tok = jnp.asarray([seq0[t0], seq1[t1]], jnp.int32)
        lg, big = model.decode_step(params, big, tok, active=act)
        errs += [_rel(lg[0], want0[t0]), _rel(lg[1], want1[t1])]
    assert max(errs) < TOL_LOGITS, errs


def test_engine_streams_match_reference_through_prefix_hits():
    """Through ContinuousBatchingEngine (paged arena, radix cache, fused
    decode): requests sharing a 16-token prefix hit the radix tree, and
    every served token is within TOL_LOGITS of the reference's best
    logit at its position."""
    cfg, params = CFG, _params()
    eng = ContinuousBatchingEngine(make_model(cfg, remat=False), params,
                                   max_batch=3, buckets=(16, 32),
                                   max_decode_len=16, page_size=PS)
    assert eng.paged
    rng = np.random.default_rng(1)
    prefix = rng.integers(0, cfg.vocab_size, 16)
    prompts = [np.concatenate([prefix, rng.integers(0, cfg.vocab_size, n)])
               .astype(np.int32) for n in (3, 9, 5, 12)]
    for i, p in enumerate(prompts):
        eng.submit(Request(rid=i, prompt=p, max_new_tokens=12,
                           t_arrival=0.05 * i))
    done = eng.run()
    assert eng.stats["prefix_hits"] >= 1
    for r in done:
        seq = np.concatenate([r.prompt, r.tokens_out[:-1]]).astype(np.int32)
        lg = np.asarray(ref.forward(cfg, params, seq))[len(r.prompt) - 1:]
        gap = lg.max(-1) - lg[np.arange(len(r.tokens_out)), r.tokens_out]
        assert gap.max() < TOL_LOGITS * np.abs(lg).max(), (r.rid, gap)


# -- (b) the router --------------------------------------------------------------

def test_sigmoid_router_bias_selects_weights_from_unbiased_scores():
    cfg = CFG
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.normal(0, 1, (32, cfg.d_model)), jnp.float32)
    p = {"router": jnp.asarray(rng.normal(0, 0.3, (cfg.d_model,
                                                   cfg.n_experts)),
                               jnp.float32),
         "router_bias": jnp.zeros((cfg.n_experts,), jnp.float32)}
    w0, ids0 = moe_mod.sigmoid_route(x, p, cfg)
    # a bias that lifts expert 0 above every score chooses it everywhere
    p1 = dict(p, router_bias=jnp.zeros((cfg.n_experts,)).at[0].set(10.0))
    w1, ids1 = moe_mod.sigmoid_route(x, p1, cfg)
    assert (np.asarray(ids1) == 0).any(-1).all()
    assert not (np.asarray(ids0) == 0).any(-1).all()
    scores = jax.nn.sigmoid(x @ p["router"])
    for w, ids in ((w0, ids0), (w1, ids1)):
        chosen = np.take_along_axis(np.asarray(scores), np.asarray(ids), -1)
        want = chosen / chosen.sum(-1, keepdims=True) * cfg.routed_scale
        np.testing.assert_allclose(np.asarray(w), want, rtol=1e-5)
        np.testing.assert_allclose(np.asarray(w).sum(-1), cfg.routed_scale,
                                   rtol=1e-5)
    wr, idr = ref.route(cfg, p1, x)
    np.testing.assert_array_equal(np.asarray(ids1), np.asarray(idr))
    np.testing.assert_allclose(np.asarray(w1), np.asarray(wr), rtol=1e-5)


# -- (c) the shares sum to the whole layer ---------------------------------------

def test_expert_shares_sum_to_the_uncut_layer():
    """16 experts split over 8 shares of 2: the 8 shares' outputs, the
    shared experts counted once, add up to the reference layer holding all
    16."""
    whole = dataclasses.replace(CFG, n_experts=16, top_k=4, experts_held=16,
                                expert_offset=0)
    p = jax.tree.map(lambda a: a[0],
                     _params(whole, seed=3)["scan"]["b0"]["ffn"])
    rng = np.random.default_rng(4)
    x = jnp.asarray(rng.normal(0, 1, (1, 24, whole.d_model)), jnp.bfloat16)
    total = jnp.zeros((24, whole.d_model), jnp.float32)
    for i in range(8):  # the shared experts on the first chip only
        cfg = dataclasses.replace(whole, experts_held=2, expert_offset=2 * i,
                                  n_shared_experts=2 if i == 0 else 0)
        ps = dict(p, **{n: p[n][2 * i:2 * i + 2] for n in ("wi", "wg", "wo")})
        y, _ = moe_mod.moe_share(x, ps, cfg)
        total = total + y[0].astype(jnp.float32)
    want = ref.moe(whole, p, x[0].astype(jnp.float32))
    assert _rel(total, want) < TOL_LAYER


def test_expert_share_layer_matches_reference():
    cfg = CFG
    p = jax.tree.map(lambda a: a[0], _params()["scan"]["b0"]["ffn"])
    x = jnp.asarray(np.random.default_rng(5).normal(0, 1, (2, 9, 64)),
                    jnp.bfloat16)
    y, counts = moe_mod.moe_share(x, p, cfg)
    for b in range(2):
        assert _rel(y[b], ref.moe(cfg, p, x[b].astype(jnp.float32))) \
            < TOL_LAYER


@pytest.mark.parametrize("sizes,offset", [
    ([3, 0, 5, 2, 7, 0, 4, 1], 2),   # groups start mid-tile, empty ones held
    ([0, 9, 0, 0, 0, 0, 9, 4], 4),   # no rows for the first two held
    ([6, 6, 0, 0, 0, 0, 6, 4], 2),   # no row held at all
    ([2, 3, 4, 5, 6, 1, 1, 0], 0),
], ids=["mid_tile", "held_empty_first", "none_held", "offset_0"])
def test_expert_gmm_interpret_matches_oracle(sizes, offset):
    """The grouped matmul (megablox's gmm with a group offset, interpret)
    against the plain jnp form: held experts' rows multiplied by their
    own weights, every other row (other experts, padding past the
    groups) exactly zero."""
    from repro.kernels import ops
    rng = np.random.default_rng(sum(sizes))
    m, k, n, g = 40, 32, 24, 4
    lhs = jnp.asarray(rng.normal(0, 1, (m, k)), jnp.bfloat16)
    rhs = jnp.asarray(rng.normal(0, 1, (g, k, n)), jnp.bfloat16)
    sz = jnp.asarray(sizes, jnp.int32)
    got = np.asarray(ops.expert_gmm(lhs, rhs, sz, offset, impl="interpret"),
                     np.float32)
    want = np.asarray(ops.expert_gmm(lhs, rhs, sz, offset, impl="ref"),
                      np.float32)
    np.testing.assert_allclose(got, want, rtol=1e-2, atol=1e-2)
    ends = np.cumsum(sizes)
    held = np.zeros(m, bool)
    held[ends[offset] - sizes[offset]:ends[offset + g - 1]] = True
    assert (got[~held] == 0).all()
    assert np.abs(got[held]).min(initial=1.0) > 0 or not held.any()


# -- (d) the leading dense layer plan -------------------------------------------

def test_leading_dense_layer_sits_outside_the_scan():
    cfg, params = CFG, _params()
    assert layer_plan(cfg) == (2, 0, ["attn"])  # the 2 MoE layers scanned
    lead = params["lead"]["0"]
    assert lead["ffn"]["wi"].shape == (cfg.d_model, cfg.dense_d_ff)
    assert "router" not in lead["ffn"]
    assert params["scan"]["b0"]["ffn"]["wi"].shape == (
        2, cfg.experts_held, cfg.d_model, cfg.d_ff)
    model = make_model(cfg)
    paged = model.init_paged_cache(2, 9, PS, 4)
    assert paged["lead"]["0"]["ckv"].shape == (9, PS, cfg.latent_stride)
    assert paged["scan"]["b0"]["ckv"].shape == (2, 9, PS, cfg.latent_stride)
    assert set(_layers(paged)) == {"lead", "scan", "tail"}
    dense = model.init_cache(2, 16)
    assert dense["lead"]["0"]["ckv"].shape == (2, 16, cfg.latent_stride)
    # configs without leading dense layers keep their trees as they were
    gqa = dataclasses.replace(cfg, kv_lora_rank=0, first_k_dense=0,
                              experts_held=0,
                              n_kv_heads=2, head_dim=16)
    assert "lead" not in init_params(gqa, jax.random.PRNGKey(0))
    assert set(make_model(gqa).init_paged_cache(2, 9, PS, 4)) == {
        "scan", "tail", "pos", "pt"}
    # the scan and the leading layer both run: the full forward matches
    lg = make_model(cfg, remat=False).forward_logits(
        params, tokens=jnp.asarray(np.arange(12)[None] % 256))
    assert _rel(lg[0], ref.forward(cfg, params, np.arange(12))) < TOL_LOGITS


# -- (g) page bytes --------------------------------------------------------------

def test_kv_page_bytes_of_a_latent_row():
    """A latent row as stored: kv rank + rope dims padded to whole
    128-lane tiles (40 -> 128 here; Moonlight's 576 -> 640)."""
    assert kv_page_bytes(CFG, 16, "bf16") == 3 * 16 * 128 * 2
    moon = dataclasses.replace(CFG, n_layers=27, kv_lora_rank=512,
                               qk_rope_head_dim=64, qk_nope_head_dim=128,
                               head_dim=0)
    assert kv_page_bytes(moon, 1, "bf16") == 27 * 1280
    with pytest.raises(ValueError, match="bf16 only"):
        kv_page_bytes(CFG, 16, "int8")


# -- (h) refusals -----------------------------------------------------------------

class _Plan:  # any plan: the refusal comes before the plan is read
    mode = "serve"


@pytest.mark.parametrize("kw,match", [
    ({"kv_dtype": "int8"}, "int8"),
    ({"spec_config": {"draft_model": None, "draft_params": None}},
     "verify"),
    ({"plan": _Plan()}, "ClusterPlan"),
    ({"disagg": (1, 1)}, "page shipping"),
    ({"paged": False}, "paged arena only"),
], ids=["int8_arena", "speculative", "plan", "disagg", "dense_slots"])
def test_mla_refuses_paths_it_lacks(kw, match):
    with pytest.raises(ValueError, match=match):
        ContinuousBatchingEngine(make_model(CFG), _params(), max_batch=2,
                                 buckets=(16,), max_decode_len=8, **kw)


# -- (i) the counters -------------------------------------------------------------

def test_counters_match_a_hand_count():
    """Two lanes, one admitted late, through the engine: the three counters
    equal a count made from the reference router over each decode step's
    inputs (the lanes' tokens at their positions, every MoE layer)."""
    cfg, params = CFG, _params()
    eng = ContinuousBatchingEngine(make_model(cfg, remat=False), params,
                                   max_batch=2, buckets=(16,),
                                   max_decode_len=8, page_size=PS,
                                   decode_horizon=4)
    rng = np.random.default_rng(6)
    reqs = [Request(rid=0, prompt=rng.integers(0, 256, 7).astype(np.int32),
                    max_new_tokens=6),
            Request(rid=1, prompt=rng.integers(0, 256, 11).astype(np.int32),
                    max_new_tokens=3, t_arrival=0.0)]
    for r in reqs:
        eng.submit(r)
    eng.run()
    routed = held = hit = 0
    lo, hi = cfg.expert_offset, cfg.expert_offset + cfg.experts_held
    # each decode step's token of a lane is the input at its position:
    # the first generated token onward, all but the last (never fed)
    inputs = {r.rid: (len(r.prompt), r.tokens_out[:-1]) for r in reqs}
    steps = {}  # decode step index -> [(rid, position)]
    for rid, (n, toks) in inputs.items():
        for j in range(len(toks)):
            steps.setdefault(j, []).append((rid, n + j))
    for r in reqs:  # per-layer routing of each position, from the reference
        seq = np.concatenate([r.prompt, r.tokens_out[:-1]]).astype(np.int32)
        r.route = _routes(cfg, params, seq)
    by_rid = {r.rid: r for r in reqs}
    for j, lanes in steps.items():
        for layer in range(2):
            experts = set()
            for rid, pos in lanes:
                ids = by_rid[rid].route[layer][pos]
                routed += cfg.top_k
                mine = [e for e in ids if lo <= e < hi]
                held += len(mine)
                experts |= set(mine)
            hit += len(experts)
    got = [eng.stats[k] for k in moe_mod.MOE_COUNTERS]
    # lanes decode in step with each other here (both admitted at once),
    # so a step's experts are the union over both lanes
    assert got == [routed, held, hit], (got, [routed, held, hit])
    assert eng.stats["device_syncs"] == eng.stats["decode_dispatches"] + 2


def _routes(cfg, params, seq):
    """The reference's expert ids at every position, per MoE layer."""
    emb = params["embed"]
    x = jnp.asarray(emb["tok"], jnp.float32)[jnp.asarray(seq)]
    out = []
    for p, dense in ref.layers(params):
        x = x + ref.attention(cfg, p["mix"], ref.rms(x, p["norm1"]["g"]))
        z = ref.rms(x, p["norm2"]["g"])
        f = p["ffn"]
        if dense:
            x = x + ref.swiglu(z, f["wi"], f["wg"], f["wo"])
        else:
            out.append(np.asarray(ref.route(cfg, f, z)[1]).tolist())
            x = x + ref.moe(cfg, f, z)
    return out
