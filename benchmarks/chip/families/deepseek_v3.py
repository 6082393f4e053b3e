"""DeepSeek-V3's block (model_type "deepseek_v3"), as Moonlight-16B-A3B
publishes it: multi-head latent attention, leading dense layers, then
sparse-expert layers routed by `noaux_tc` with shared experts.

One chip's share of an expert-parallel deployment: every layer's
attention, dense MLP, router and shared experts, and the routed experts
[expert_offset, expert_offset + n_routed_experts) of the
`n_routed_experts_published` the router scores.  The reference is given
the same share: what the absent experts add is left out of both.

The interface is families/llama.py's (`shapes`, `served_params`,
`model_config`, `logits`, `CONTROLS` and the work counts).

The reference, per layer, in float32 at HIGHEST precision:

- attention, expanded: q = x Wq split into q_nope (128) and q_rope (64);
  [c_kv | k_rope] = x Wkv_a, c_kv RMS-normed (gain 1); [k_nope | v] per
  head = c_kv Wkv_b; rope on q_rope and on the one k_rope every head
  shares; scores [q_nope | q_rope] . [k_nope | k_rope] / sqrt(192),
  causal softmax, p v, then Wo;
- the first `first_k_dense_replace` layers: SwiGLU of width
  intermediate_size;
- then: scores sigmoid(x router); experts = top-k of scores + bias;
  weights = the chosen scores normalized over the k, times
  routed_scaling_factor; each held expert's SwiGLU (width
  moe_intermediate_size) times its weight, summed; plus the shared
  experts' SwiGLU (width moe_intermediate_size * n_shared_experts).

Departure from modeling_deepseek: RoPE is rotate-half over the rope dims
(modeling_deepseek first de-interleaves them; with random weights that is
a fixed permutation of those columns of Wq and Wkv_a).
"""
from __future__ import annotations

import functools
import json
from dataclasses import dataclass
from pathlib import Path
from typing import List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

import costs
import reference as R
import weights as W
from repro.configs.base import ModelConfig

__all__ = ["Shapes", "shapes", "served_params", "model_config", "logits",
           "CONTROLS", "token_flops", "prefill_flops", "head_flops",
           "attn_flops_per_key", "kv_bytes_per_key", "held_pairs_per_token",
           "expert_bytes", "expert_pair_flops"]

CONTROLS = ("w8a8", "fp8")
# fixed ids, so a tensor's values never depend on which others exist
_IDS = {"wq": 1, "wkv_a": 2, "wkv_b": 3, "wo": 4, "wi": 5, "wg": 6, "wd": 7,
        "router": 8, "bias": 9, "swi": 10, "swg": 11, "swd": 12}
EXPERT_FOLD = 1 << 16  # folded with the expert's number for its weights


@dataclass(frozen=True)
class Shapes:
    name: str
    d: int             # hidden_size
    ff: int            # intermediate_size (the leading dense layers)
    moe_ff: int        # moe_intermediate_size
    layers: int
    dense: int         # first_k_dense_replace
    heads: int
    kv_rank: int       # kv_lora_rank
    nope: int          # qk_nope_head_dim
    rope: int          # qk_rope_head_dim
    v: int             # v_head_dim
    experts: int       # the router's width (published n_routed_experts)
    held: int          # experts this chip holds
    offset: int        # the first of them
    top_k: int
    shared: int        # n_shared_experts
    scale: float       # routed_scaling_factor
    vocab: int
    eps: float
    theta: float
    std: float
    bias_std: float

    @property
    def qk(self) -> int:
        return self.nope + self.rope

    @property
    def row(self) -> int:  # one latent cache row
        return self.kv_rank + self.rope


def shapes(path: Path, name: str) -> Shapes:
    if "kv_lora_rank" not in ModelConfig.__dataclass_fields__:
        raise SystemExit("bench: this program has no latent attention "
                         "(ModelConfig.kv_lora_rank), so deepseek_v3 "
                         "cannot run")
    c = json.loads(Path(path).read_text())
    return Shapes(name=name, d=int(c["hidden_size"]),
                  ff=int(c["intermediate_size"]),
                  moe_ff=int(c["moe_intermediate_size"]),
                  layers=int(c["num_hidden_layers"]),
                  dense=int(c["first_k_dense_replace"]),
                  heads=int(c["num_attention_heads"]),
                  kv_rank=int(c["kv_lora_rank"]),
                  nope=int(c["qk_nope_head_dim"]),
                  rope=int(c["qk_rope_head_dim"]), v=int(c["v_head_dim"]),
                  experts=int(c["n_routed_experts_published"]),
                  held=int(c["n_routed_experts"]),
                  offset=int(c["held_experts_offset"]),
                  top_k=int(c["num_experts_per_tok"]),
                  shared=int(c["n_shared_experts"]),
                  scale=float(c["routed_scaling_factor"]),
                  vocab=int(c["vocab_size"]), eps=float(c["rms_norm_eps"]),
                  theta=float(c["rope_theta"]),
                  std=float(c["initializer_range"]),
                  bias_std=float(c["selection_bias_std"]))


# -- weights and the program -------------------------------------------------

def _f32(key, ident: int, shape, std: float) -> jax.Array:
    return jax.random.normal(jax.random.fold_in(key, ident), shape,
                             jnp.float32) * std


def attention(s: Shapes, k) -> dict:
    return {"wq": W.draw(k, _IDS["wq"], (s.d, s.heads * s.qk), s.std),
            "wkv_a": W.draw(k, _IDS["wkv_a"], (s.d, s.row), s.std),
            "wkv_b": W.draw(k, _IDS["wkv_b"],
                            (s.kv_rank, s.heads * (s.nope + s.v)), s.std),
            "wo": W.draw(k, _IDS["wo"], (s.heads * s.v, s.d), s.std)}


def layer(s: Shapes, key: jax.Array, i, dense: bool) -> dict:
    """Layer i's tensors, (in, out) layout: bfloat16 matrices; a MoE
    layer's router and selection bias in float32 and its held experts
    stacked (held, in, out)."""
    k = jax.random.fold_in(key, i)
    w = attention(s, k)

    def expert(e):
        ke = jax.random.fold_in(k, EXPERT_FOLD + e)
        return {n: W.draw(ke, _IDS[n], shape, s.std) for n, shape in
                (("wi", (s.d, s.moe_ff)), ("wg", (s.d, s.moe_ff)),
                 ("wd", (s.moe_ff, s.d)))}

    if dense:
        w.update(wi=W.draw(k, _IDS["wi"], (s.d, s.ff), s.std),
                 wg=W.draw(k, _IDS["wg"], (s.d, s.ff), s.std),
                 wd=W.draw(k, _IDS["wd"], (s.ff, s.d), s.std))
        return w
    fs = s.moe_ff * s.shared
    ex = jax.lax.map(expert, s.offset + jnp.arange(s.held))
    w.update(router=_f32(k, _IDS["router"], (s.d, s.experts), s.std),
             bias=_f32(k, _IDS["bias"], (s.experts,), s.bias_std),
             ewi=ex["wi"], ewg=ex["wg"], ewd=ex["wd"],
             swi=W.draw(k, _IDS["swi"], (s.d, fs), s.std),
             swg=W.draw(k, _IDS["swg"], (s.d, fs), s.std),
             swd=W.draw(k, _IDS["swd"], (fs, s.d), s.std))
    return w


def _embedding(s: Shapes, key: jax.Array) -> dict:
    return W.embedding(key, s.vocab, s.d, s.std, False)


def served_params(s: Shapes, seed: int):
    """The served tree, made on the device in one jitted call, the MoE
    stack one layer at a time: the leading dense layers under "lead", the
    rest stacked under "scan", norm gains 1."""

    def make(key):
        g = jnp.ones((s.d,), jnp.float32)

        def mix(w):
            return {"wq": w["wq"], "wkv_a": w["wkv_a"],
                    "kv_norm": {"g": jnp.ones((s.kv_rank,), jnp.float32)},
                    "wkv_b": w["wkv_b"], "wo": w["wo"]}

        def dense(i):
            w = layer(s, key, i, True)
            return {"norm1": {"g": g}, "mix": mix(w), "norm2": {"g": g},
                    "ffn": {"wi": w["wi"], "wo": w["wd"], "wg": w["wg"]}}

        def moe(i):
            w = layer(s, key, i, False)
            return {"b0": {"norm1": {"g": g}, "mix": mix(w),
                           "norm2": {"g": g},
                           "ffn": {"router": w["router"],
                                   "wi": w["ewi"], "wo": w["ewd"],
                                   "router_bias": w["bias"],
                                   "wg": w["ewg"], "shared_wi": w["swi"],
                                   "shared_wo": w["swd"],
                                   "shared_wg": w["swg"]}}}

        return {"embed": _embedding(s, key),
                "lead": {str(i): dense(i) for i in range(s.dense)},
                "scan": jax.lax.map(moe, s.dense + jnp.arange(
                    s.layers - s.dense)),
                "tail": {},
                "final_norm": {"g": jnp.ones((s.d,), jnp.float32)}}

    return jax.jit(make)(W.seed_key(seed))


def model_config(s: Shapes) -> ModelConfig:
    return ModelConfig(name=s.name, family="moe", n_layers=s.layers,
                       d_model=s.d, n_heads=s.heads, n_kv_heads=1,
                       d_ff=s.moe_ff, vocab_size=s.vocab,
                       kv_lora_rank=s.kv_rank, qk_nope_head_dim=s.nope,
                       qk_rope_head_dim=s.rope, v_head_dim=s.v,
                       rope_theta=s.theta, causal=True, mlp_style="swiglu",
                       act="silu", n_experts=s.experts, top_k=s.top_k,
                       moe_every=1, n_shared_experts=s.shared,
                       routed_scale=s.scale,
                       experts_held=s.held, expert_offset=s.offset,
                       first_k_dense=s.dense, dense_d_ff=s.ff,
                       tie_embeddings=False, norm="rmsnorm")


# -- the reference -------------------------------------------------------------

def _swiglu(mm, z, wi, wg, wd):
    return mm(jax.nn.silu(mm(z, wg)) * mm(z, wi), wd)


@functools.partial(jax.jit, static_argnames=("s", "quant", "moe"))
def _block(x, w, valid, s: Shapes, quant: str, moe: bool):
    mm = R.projection(quant, valid)
    n, h = x.shape[0], s.heads
    z = R.rms(x, s.eps)
    q = mm(z, w["wq"]).reshape(n, h, s.qk)
    kva = mm(z, w["wkv_a"])
    c = R.rms(kva[:, :s.kv_rank], s.eps)
    k_rope = R.rope(kva[:, None, s.kv_rank:], s.theta)  # (n, 1, rope)
    kv = mm(c, w["wkv_b"]).reshape(n, h, s.nope + s.v)
    q = jnp.concatenate([q[..., :s.nope], R.rope(q[..., s.nope:], s.theta)],
                        -1)
    k = jnp.concatenate([kv[..., :s.nope],
                         jnp.broadcast_to(k_rope, (n, h, s.rope))], -1)
    sc = jnp.einsum("qhd,khd->hqk", q, k, precision=R.HI) / np.sqrt(s.qk)
    causal = jnp.arange(n)[:, None] >= jnp.arange(n)[None, :]
    p = jax.nn.softmax(jnp.where(causal[None], sc, -jnp.inf), axis=-1)
    o = jnp.einsum("hqk,khd->qhd", p, kv[..., s.nope:],
                   precision=R.HI).reshape(n, h * s.v)
    x = x + mm(o, w["wo"])
    z = R.rms(x, s.eps)
    if not moe:
        return x + _swiglu(mm, z, w["wi"], w["wg"], w["wd"])
    scores = jax.nn.sigmoid(jnp.matmul(z, w["router"], precision=R.HI))
    _, ids = jax.lax.top_k(scores + w["bias"], s.top_k)
    gate = jnp.take_along_axis(scores, ids, -1)
    gate = gate / (gate.sum(-1, keepdims=True) + 1e-20) * s.scale
    held = s.offset + jnp.arange(s.held)
    gate = jnp.sum(jnp.where(ids[..., None] == held, gate[..., None], 0.0),
                   1)  # (n, held): each held expert's weight, 0 if unchosen
    ye = jax.vmap(lambda wi, wg, wd: _swiglu(mm, z, wi, wg, wd))(
        w["ewi"], w["ewg"], w["ewd"])  # (held, n, d)
    y = (_swiglu(mm, z, w["swi"], w["swg"], w["swd"])
         + jnp.einsum("end,ne->nd", ye, gate, precision=R.HI))
    return x + y


def _upcast(w: dict, quant: str) -> dict:
    """The matrices as the control multiplies them (R.upcast, each expert
    on its own); the router and bias stay float32."""
    experts = ("ewi", "ewg", "ewd")
    out = {k: v for k, v in w.items() if k in ("router", "bias")}
    out.update(R.upcast({k: v for k, v in w.items()
                         if k not in out and k not in experts}, quant))
    for k in experts:
        if k in w:
            out[k] = jax.vmap(lambda m: R.upcast({"m": m}, quant)["m"])(w[k])
    return out


@functools.partial(jax.jit, static_argnames=("s", "quant", "dense"))
def _block_weights(key, i, s: Shapes, quant: str, dense: bool):
    return _upcast(layer(s, key, i, dense), quant)


def logits(s: Shapes, seed: int, seqs: Sequence[np.ndarray],
           positions: Sequence[np.ndarray], quant: str = ""
           ) -> List[np.ndarray]:
    """Float32 logits (or, with `quant` one of CONTROLS, a control's) of
    each token sequence at the given positions, (len(positions[j]),
    vocab) each, a layer at a time."""
    key = W.seed_key(seed)
    emb = jax.jit(lambda k: _embedding(s, k))(key)
    xs = R.embed(emb["tok"], seqs)
    # one length for all (causal: trailing pads change nothing), so a
    # layer kind compiles once, not once per length
    n = max(x.shape[0] for x in xs)
    xs = [jnp.pad(x, ((0, n - x.shape[0]), (0, 0))) for x in xs]
    for i in range(s.layers):
        w = _block_weights(key, i, s, quant, i < s.dense)
        xs = [_block(x, w, len(q), s, quant, i >= s.dense)
              for x, q in zip(xs, seqs)]
        del w
    return R.head_logits(xs, positions, emb, s.eps)


# -- work counts -------------------------------------------------------------

def _attn_params(s: Shapes) -> int:
    """q, kv_a, kv_b and o: 13.76 M a layer at Moonlight's widths."""
    return (s.d * s.heads * s.qk + s.d * s.row
            + s.kv_rank * s.heads * (s.nope + s.v) + s.heads * s.v * s.d)


def held_pairs_per_token(s: Shapes) -> float:
    """(token, expert) pairs a token sends to this chip's experts in one
    MoE layer, on average under uniform routing: top_k * held / experts
    (6 * 8/64 = 0.75)."""
    return s.top_k * s.held / s.experts


def expert_pair_flops(s: Shapes) -> int:
    """One (token, expert) pair through a SwiGLU expert: 2*3*d*moe_ff."""
    return 6 * s.d * s.moe_ff


def expert_bytes(s: Shapes) -> int:
    """One expert's three matrices in bfloat16 (17.3 MB at Moonlight's)."""
    return 3 * s.d * s.moe_ff * 2


def _token_matmul_flops(s: Shapes) -> float:
    """One token through every layer's projections: attention, the dense
    MLP, the router, the shared experts, and the held experts' share of
    its top-k pairs (held_pairs_per_token, not the pairs it really
    sent)."""
    dense = 2 * (_attn_params(s) + 3 * s.d * s.ff)
    moe = (2 * (_attn_params(s) + s.d * s.experts
                + 3 * s.d * s.moe_ff * s.shared)
           + held_pairs_per_token(s) * expert_pair_flops(s))
    return s.dense * dense + (s.layers - s.dense) * moe


def head_flops(s: Shapes) -> int:
    return 2 * s.d * s.vocab


def attn_flops_per_key(s: Shapes) -> int:
    """Absorbed decode, one query against one cached row, over every
    layer: 2*heads*(row + kv_rank), the latent q . row and p . c_kv."""
    return 2 * s.heads * (s.row + s.kv_rank) * s.layers


def kv_bytes_per_key(s: Shapes) -> int:
    """One cached latent row (kv_rank + rope, bfloat16), every layer."""
    return s.row * costs.KV_BYTES * s.layers


def token_flops(s: Shapes, keys: int, head: bool) -> int:
    """One token's decode forward pass attending to `keys` cached rows;
    `head` when its logits are read."""
    return int(round(_token_matmul_flops(s))) + (
        attn_flops_per_key(s) * keys + (head_flops(s) if head else 0))


def prefill_flops(s: Shapes, n: int) -> int:
    """A causal prefill of n tokens, attention expanded per head
    (2*heads*(qk + v) a query-key pair a layer), last logits read."""
    return (int(round(_token_matmul_flops(s) * n))
            + 2 * s.heads * (s.qk + s.v) * s.layers * n * (n + 1) // 2
            + head_flops(s))
