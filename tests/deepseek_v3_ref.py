"""Plain float32 reference of the DeepSeek-V3 block that Moonlight-16B-A3B
publishes (modeling_deepseek), for the tier-1 tests.

Straight `jax.numpy` over one sequence, float32, every product at HIGHEST
precision: no kernel, cache, paging or batching.  It reads the weights of
the program's parameter tree (`models/transformer.init_params` layout) and
nothing else of the program.

- Attention (MLA, no q LoRA): q = x Wq, split per head into q_nope and
  q_rope; [c_kv | k_rope] = x Wkv_a; c_kv is RMS-normed; c_kv Wkv_b gives
  each head's [k_nope | v]; RoPE on q_rope and on the single k_rope all
  heads share; softmax(q . k / sqrt(qk_nope + qk_rope)) causal; p v; Wo.
- The first `first_k_dense` layers: SwiGLU of width dense_d_ff.
- The rest: scores = sigmoid(x router); experts = top-k of scores + the
  selection bias; weights = the chosen scores over their sum, times
  routed_scale; each chosen expert's SwiGLU times its weight, summed over
  the experts this chip holds (`held`, default the config's share); plus
  the shared experts' SwiGLU, once.

Departures from modeling_deepseek, the program's alike: RoPE is
rotate-half over the rope dims (modeling_deepseek de-interleaves them
first, a fixed permutation of those weight columns); RMSNorm's epsilon is
1e-6 (the program's, fixed); only the held experts' part of a MoE layer is
computed (the share the configuration states).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
EPS = 1e-6


def _mm(x, w):
    return jnp.matmul(x, jnp.asarray(w, jnp.float32), precision=HI)


def rms(x, g):
    x = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + EPS)
    return x * jnp.asarray(g, jnp.float32)


def rope(x, theta):
    """x: (S, heads, d) at positions 0..S-1, rotate-half."""
    s, _, d = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(cfg, p, x):
    s, h, r = x.shape[0], cfg.n_heads, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    q = _mm(x, p["wq"]).reshape(s, h, dn + dr)
    kva = _mm(x, p["wkv_a"])
    c = rms(kva[:, :r], p["kv_norm"]["g"])
    kv = _mm(c, p["wkv_b"]).reshape(s, h, dn + dv)
    k_rope = rope(kva[:, None, r:], cfg.rope_theta)
    q = jnp.concatenate([q[..., :dn], rope(q[..., dn:], cfg.rope_theta)], -1)
    k = jnp.concatenate([kv[..., :dn],
                         jnp.broadcast_to(k_rope, (s, h, dr))], -1)
    sc = jnp.einsum("qhd,khd->hqk", q, k, precision=HI) / np.sqrt(dn + dr)
    causal = jnp.tril(jnp.ones((s, s), bool))
    pr = jax.nn.softmax(jnp.where(causal[None], sc, -jnp.inf), -1)
    o = jnp.einsum("hqk,khd->qhd", pr, kv[..., dn:], precision=HI)
    return _mm(o.reshape(s, h * dv), p["wo"])


def swiglu(x, wi, wg, wo):
    return _mm(jax.nn.silu(_mm(x, wg)) * _mm(x, wi), wo)


def route(cfg, p, x):
    """(weights (S, k), expert ids (S, k)) of noaux_tc routing."""
    scores = jax.nn.sigmoid(_mm(x, p["router"]))
    _, ids = jax.lax.top_k(scores + p["router_bias"], cfg.top_k)
    w = jnp.take_along_axis(scores, ids, -1)
    return w / w.sum(-1, keepdims=True) * cfg.routed_scale, ids


def moe(cfg, p, x, held=None, shared=True):
    """The MoE layer's output: the experts in `held` (ids, default the
    config's share, whose weights are p's in order), plus the shared
    experts when `shared`."""
    if held is None:
        held = range(cfg.expert_offset, cfg.expert_offset + cfg.experts_held)
    w, ids = route(cfg, p, x)
    y = (swiglu(x, p["shared_wi"], p["shared_wg"], p["shared_wo"]) if shared
         else jnp.zeros_like(x))
    for j, e in enumerate(held):
        we = jnp.sum(jnp.where(ids == e, w, 0.0), -1)
        y = y + we[:, None] * swiglu(x, p["wi"][j], p["wg"][j], p["wo"][j])
    return y


def layers(params):
    """(block params, is_dense) of every layer in order."""
    out = [(params["lead"][k], True) for k in sorted(params.get("lead", {}),
                                                     key=int)]
    scan = params["scan"]["b0"]
    n = jax.tree.leaves(scan)[0].shape[0]
    out += [(jax.tree.map(lambda a, i=i: a[i], scan), False)
            for i in range(n)]
    return out


def forward(cfg, params, tokens):
    """Float32 logits (S, vocab) of one token sequence."""
    emb = params["embed"]
    x = jnp.asarray(emb["tok"], jnp.float32)[jnp.asarray(tokens)]
    for p, dense in layers(params):
        x = x + attention(cfg, p["mix"], rms(x, p["norm1"]["g"]))
        z = rms(x, p["norm2"]["g"])
        f = p["ffn"]
        x = x + (swiglu(z, f["wi"], f["wg"], f["wo"]) if dense
                 else moe(cfg, f, z))
    x = rms(x, params["final_norm"]["g"])
    return _mm(x, emb["head"])
