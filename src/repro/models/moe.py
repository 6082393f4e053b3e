"""Mixture-of-Experts FFN: top-k routing with capacity-based gather/scatter.

The paper's GMI Scatter/Gather collectives (§5, Fig. 6) are exactly the MoE
dispatch/combine pattern: a router scatters token blocks to expert kernels
and gathers their outputs.  We implement the TPU-native version: tokens are
ranked per expert (sort-free cumsum ranking), gathered into a dense
(groups, experts, capacity, d_model) layout that shards cleanly — groups
over the `data` axes, experts over `model` — so SPMD lowers dispatch/combine
into all-to-alls over the GMI communicator axes.

Capacity-dropping (GShard-style, capacity_factor>=1.0) keeps shapes static;
dropped tokens pass through the residual only.  Router runs in f32.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.models.layers import act_fn, dense_init

Params = Dict[str, Any]


def moe_init(key, cfg) -> Params:
    """Router over all n_experts; expert weights for the experts held
    (all of them unless cfg.experts_held says otherwise)."""
    d, f = cfg.d_model, cfg.d_ff
    e = cfg.experts_held or cfg.n_experts
    ks = jax.random.split(key, 5)
    s_in, s_out = 1.0 / math.sqrt(d), 1.0 / math.sqrt(f)
    p = {
        "router": (jax.random.normal(ks[0], (d, cfg.n_experts)) * 0.02
                   ).astype(jnp.float32),
        "wi": (jax.random.normal(ks[1], (e, d, f)) * s_in).astype(jnp.bfloat16),
        "wo": (jax.random.normal(ks[2], (e, f, d)) * s_out).astype(jnp.bfloat16),
    }
    if cfg.experts_held:  # e_score_correction_bias: selection only
        p["router_bias"] = jnp.zeros((cfg.n_experts,), jnp.float32)
    if cfg.mlp_style == "swiglu":
        p["wg"] = (jax.random.normal(ks[3], (e, d, f)) * s_in).astype(jnp.bfloat16)
    if cfg.n_shared_experts:
        fs = cfg.d_ff * cfg.n_shared_experts
        p["shared_wi"] = dense_init(ks[4], d, fs)
        p["shared_wo"] = dense_init(jax.random.fold_in(ks[4], 1), fs, d)
        if cfg.mlp_style == "swiglu":
            p["shared_wg"] = dense_init(jax.random.fold_in(ks[4], 2), d, fs)
    return p


def _capacity(cfg, tokens_per_group: int) -> int:
    c = int(math.ceil(cfg.top_k * tokens_per_group * cfg.capacity_factor
                      / cfg.n_experts))
    return max(c, 1)


def moe_ffn(x: jax.Array, p: Params, cfg) -> Tuple[jax.Array, jax.Array]:
    """x: (B, S, D). Returns (y, aux_loss). Groups = batch rows."""
    bsz, s, d = x.shape
    e, k = cfg.n_experts, cfg.top_k
    cap = _capacity(cfg, s)
    act = act_fn(cfg.act)

    logits = jnp.einsum("bsd,de->bse", x.astype(jnp.float32), p["router"])
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_ids = jax.lax.top_k(probs, k)  # (B,S,k)
    gate_vals = gate_vals / jnp.maximum(
        gate_vals.sum(-1, keepdims=True), 1e-9)  # renormalize top-k

    # load-balance aux loss (Switch): e * sum_e f_e * p_e
    me = jnp.mean(probs, axis=(0, 1))  # (E,)
    one_hot_top1 = jax.nn.one_hot(expert_ids[..., 0], e)
    ce = jnp.mean(one_hot_top1, axis=(0, 1))
    aux = e * jnp.sum(me * ce)

    # -- rank each (token, choice) within its expert (per group) ------------
    flat_ids = expert_ids.reshape(bsz, s * k)  # (B, S*k) in routing order
    onehot = jax.nn.one_hot(flat_ids, e, dtype=jnp.int32)  # (B,S*k,E)
    rank_in_expert = jnp.cumsum(onehot, axis=1) - onehot  # exclusive
    pos = jnp.sum(rank_in_expert * onehot, axis=-1)  # (B, S*k)
    keep = pos < cap
    slot = jnp.where(keep, flat_ids * cap + pos, e * cap)  # overflow slot

    # -- dispatch: gather tokens into (B, E*cap(+1), D) ----------------------
    # NB: index arrays stay rank-2 (no (B,S*k,D) broadcast index tensors —
    # those materialize in the backward pass and dwarf the activations), and
    # every (B, S*k, D) intermediate is pinned batch-sharded: the gather /
    # scatter transposes otherwise replicate the batch under SPMD
    from repro.models.shard_hints import hint

    token_idx = jnp.repeat(jnp.arange(s), k)  # (S*k,)
    src = hint(jnp.take(x, token_idx, axis=1, mode="clip"), "btd")  # (B,S*k,D)
    # per-row scatter vmapped over batch: lowers to a scatter with operand
    # batching dims, which SPMD partitions trivially over `data` (a scatter
    # whose batch coord is a *scattered* dim would all-gather the updates)
    buf = jax.vmap(
        lambda sl, sr: jnp.zeros((e * cap + 1, d), x.dtype).at[sl].set(
            sr, mode="drop"))(slot, src)
    buf = hint(buf, "btd")
    xe = buf[:, : e * cap].reshape(bsz, e, cap, d)
    xe = hint(xe, "moe")  # dispatch boundary: E -> model axis (all-to-all)

    # -- expert FFN (E sharded over `model`): all-to-all boundary ------------
    from repro.models.shard_hints import fsdp_int8_gather
    wi = fsdp_int8_gather(p["wi"])  # no-op unless int8_gather hints on
    wo = fsdp_int8_gather(p["wo"])
    hi = hint(jnp.einsum("becd,edf->becf", xe, wi), "moe")
    if cfg.mlp_style == "swiglu":
        wg = fsdp_int8_gather(p["wg"])
        hi = act(hint(jnp.einsum("becd,edf->becf", xe, wg), "moe")) * hi
    else:
        hi = act(hi)
    ye = hint(jnp.einsum("becf,efd->becd", hi, wo), "moe")

    # -- combine: gather back + weight by gates ------------------------------
    ye_flat = hint(ye.reshape(bsz, e * cap, d), "btd")
    ye_flat = jnp.concatenate(
        [ye_flat, jnp.zeros((bsz, 1, d), ye.dtype)], axis=1)
    back = jax.vmap(
        lambda yb, sb: jnp.take(yb, sb, axis=0, mode="clip"))(ye_flat, slot)
    back = hint(back, "btd")
    w = (gate_vals.reshape(bsz, s * k) * keep).astype(x.dtype)
    y = jnp.sum((back * w[..., None]).reshape(bsz, s, k, d), axis=2)

    if cfg.n_shared_experts:
        y = y + _shared_experts(x, p, cfg)

    return y, aux.astype(jnp.float32)


def _shared_experts(x, p, cfg):
    from repro.models.shard_hints import hint

    act = act_fn(cfg.act)
    hs = jnp.einsum("bsd,df->bsf", x, p["shared_wi"])
    if cfg.mlp_style == "swiglu":
        hs = act(jnp.einsum("bsd,df->bsf", x, p["shared_wg"])) * hs
    else:
        hs = act(hs)
    # serve_exact gathers / serve_psum keeps f-sharded before the
    # shared_wo reduction, mirroring the dense mlp (no-ops elsewhere)
    hs = hint(hint(hs, "gather"), "psum")
    return jnp.einsum("bsf,fd->bsd", hs, p["shared_wo"])


# counters the expert share returns, in this order (engine.stats keys)
MOE_COUNTERS = ("moe_pairs_routed", "moe_pairs_held", "moe_experts_hit")


def sigmoid_route(x: jax.Array, p: Params, cfg):
    """DeepSeek-V3 `noaux_tc` routing (n_group = topk_group = 1), float32:
    scores sigmoid(x . router); the top-k of scores + router_bias choose
    the experts; their weights are the chosen scores without the bias,
    normalized over the k, times cfg.routed_scale.  x: (T, D) ->
    (weights (T, k) f32, expert ids (T, k) int32)."""
    logits = jnp.dot(x.astype(jnp.float32), p["router"],
                     precision=jax.lax.Precision.HIGHEST)
    scores = jax.nn.sigmoid(logits)
    _, ids = jax.lax.top_k(scores + p["router_bias"], cfg.top_k)
    w = jnp.take_along_axis(scores, ids, axis=-1)
    w = w / (w.sum(-1, keepdims=True) + 1e-20) * cfg.routed_scale
    return w, ids.astype(jnp.int32)


def moe_share(x: jax.Array, p: Params, cfg, valid=None):
    """This chip's part of a sigmoid-routed expert layer, dropless.

    Routes every token over all n_experts, keeps the (token, expert)
    pairs whose expert is held here ([expert_offset, + experts_held)),
    sorts them by expert and runs the SwiGLU experts as three grouped
    matmuls over the held experts only (kernels/ops.expert_gmm; every
    held pair is computed, none dropped), then combines each token's
    pairs with their routing weights; the shared experts are added once.
    What absent experts would add is left out (no exchange: their chips
    compute it).  `valid` (B, S) marks real tokens: padding and inactive
    lanes route to no expert and are not counted.

    Returns (y, counts): counts is int32 (3,) in MOE_COUNTERS order —
    the valid tokens' pairs, those that landed on held experts, and the
    held experts with at least one pair."""
    from repro.kernels import ops as kops

    bsz, s, d = x.shape
    t, k, e = bsz * s, cfg.top_k, cfg.n_experts
    off, g = cfg.expert_offset, cfg.experts_held
    xf = x.reshape(t, d)
    w, ids = sigmoid_route(xf, p, cfg)
    ok = (jnp.ones((t,), bool) if valid is None else valid.reshape(t))
    held = (ids >= off) & (ids < off + g) & ok[:, None]  # (T, k)
    key = jnp.where(held, ids, e).reshape(-1)  # unheld pairs sort last
    order = jnp.argsort(key, stable=True)
    sizes = jnp.bincount(key, length=e + 1)[:e].astype(jnp.int32)
    xs = jnp.take(xf, order // k, axis=0)
    act = act_fn(cfg.act)
    # the kernel routes compiled only, like the paged decode kernels: its
    # interpreted grid would dominate CPU serving (tests/test_mla_moe.py
    # runs it in interpret mode against the jnp form)
    impl = "pallas" if kops.default_impl() == "pallas" else "ref"
    hi = kops.expert_gmm(xs, p["wi"], sizes, off, impl=impl)
    hi = act(kops.expert_gmm(xs, p["wg"], sizes, off, impl=impl)) * hi
    ys = kops.expert_gmm(hi, p["wo"], sizes, off, impl=impl)
    back = jnp.take(ys, jnp.argsort(order), axis=0).reshape(t, k, d)
    wk = jnp.where(held, w, 0.0)
    y = jnp.einsum("tkd,tk->td", back.astype(jnp.float32), wk)
    y = y.astype(x.dtype).reshape(bsz, s, d)
    if cfg.n_shared_experts:
        y = y + _shared_experts(x, p, cfg)
    counts = jnp.stack([k * ok.sum(), held.sum(),
                        (sizes[off:off + g] > 0).sum()]).astype(jnp.int32)
    return y, counts
