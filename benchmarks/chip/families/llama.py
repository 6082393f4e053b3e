"""The dense decoder with GQA, RoPE and SwiGLU (model_type "llama").

A family module is what the harness knows of one kind of model, found
by the configuration file's `model_type` (families/<model_type>.py):

- `shapes(path, name)`: the sizes, from the configuration file, as a
  hashable object with at least `name` and `vocab`;
- `served_params(s, seed)`: the program's parameter tree, made from the
  seed on the device in one jitted call;
- `model_config(s)`: the program's ModelConfig;
- `logits(s, seed, seqs, positions, quant="")`: the plain float32
  reference at HIGHEST precision, or a control's (`quant` one of
  `CONTROLS`), written from the published architecture;
- the work counts `token_flops`, `prefill_flops`, `head_flops`,
  `attn_flops_per_key` and `kv_bytes_per_key`, from shapes alone.
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
           "CONTROLS", "layer", "layer_matmul_params", "token_flops",
           "prefill_flops", "head_flops", "attn_flops_per_key",
           "kv_bytes_per_key"]

CONTROLS = ("w8a8", "fp8")  # the lower precisions the reference can run
# fixed ids, so a tensor's values never depend on which others exist
_IDS = {"wq": 1, "wk": 2, "wv": 3, "wo": 4, "wi": 5, "wg": 6, "wd": 7}


@dataclass(frozen=True)
class Shapes:
    name: str
    d: int          # hidden_size
    ff: int         # intermediate_size
    layers: int
    heads: int
    kv_heads: int
    head_dim: int
    vocab: int
    tied: bool
    eps: float
    theta: float
    std: float

    @property
    def q_width(self) -> int:
        return self.heads * self.head_dim

    @property
    def kv_width(self) -> int:
        return self.kv_heads * self.head_dim


def shapes(path: Path, name: str) -> Shapes:
    c = json.loads(Path(path).read_text())
    h = int(c["num_attention_heads"])
    return Shapes(name=name, d=int(c["hidden_size"]),
                  ff=int(c["intermediate_size"]),
                  layers=int(c["num_hidden_layers"]), heads=h,
                  kv_heads=int(c["num_key_value_heads"]),
                  head_dim=int(c.get("head_dim") or c["hidden_size"] // h),
                  vocab=int(c["vocab_size"]),
                  tied=bool(c["tie_word_embeddings"]),
                  eps=float(c["rms_norm_eps"]),
                  theta=float(c["rope_theta"]),
                  std=float(c["initializer_range"]))


# -- weights and the program -------------------------------------------------

def layer(s: Shapes, key: jax.Array, i) -> dict:
    """Layer i's matrices, (in, out) layout, bfloat16."""
    k = jax.random.fold_in(key, i)
    return {"wq": W.draw(k, _IDS["wq"], (s.d, s.q_width), s.std),
            "wk": W.draw(k, _IDS["wk"], (s.d, s.kv_width), s.std),
            "wv": W.draw(k, _IDS["wv"], (s.d, s.kv_width), s.std),
            "wo": W.draw(k, _IDS["wo"], (s.q_width, s.d), s.std),
            "wg": W.draw(k, _IDS["wg"], (s.d, s.ff), s.std),
            "wi": W.draw(k, _IDS["wi"], (s.d, s.ff), s.std),
            "wd": W.draw(k, _IDS["wd"], (s.ff, s.d), s.std)}


def _embedding(s: Shapes, key: jax.Array) -> dict:
    return W.embedding(key, s.vocab, s.d, s.std, s.tied)


def served_params(s: Shapes, seed: int):
    """The served model's parameter tree, made on the device in one jitted
    call (the layer stack one layer at a time, so no whole-stack float32
    copy exists): the stacked layer scan, the embedding and the final
    norm, norm gains 1."""

    def make(key):
        def one(i):
            w = layer(s, key, i)
            g = jnp.ones((s.d,), jnp.float32)
            return {"b0": {"norm1": {"g": g},
                           "mix": {n: w[n] for n in ("wq", "wk", "wv", "wo")},
                           "norm2": {"g": g},
                           "ffn": {"wi": w["wi"], "wg": w["wg"],
                                   "wo": w["wd"]}}}

        scan = jax.lax.map(one, jnp.arange(s.layers))
        return {"embed": _embedding(s, key), "scan": scan, "tail": {},
                "final_norm": {"g": jnp.ones((s.d,), jnp.float32)}}

    return jax.jit(make)(W.seed_key(seed))


def model_config(s: Shapes) -> ModelConfig:
    return ModelConfig(name=s.name, family="dense", n_layers=s.layers,
                       d_model=s.d, n_heads=s.heads, n_kv_heads=s.kv_heads,
                       d_ff=s.ff, vocab_size=s.vocab, head_dim=s.head_dim,
                       rope_theta=s.theta, local_window=0, causal=True,
                       mlp_style="swiglu", act="silu", tie_embeddings=s.tied,
                       norm="rmsnorm")


# -- the reference -------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("s", "quant"))
def _block(x, w, valid, s: Shapes, quant: str):
    mm = R.projection(quant, valid)
    n = x.shape[0]
    z = R.rms(x, s.eps)
    q = R.rope(mm(z, w["wq"]).reshape(n, s.heads, s.head_dim), s.theta)
    k = R.rope(mm(z, w["wk"]).reshape(n, s.kv_heads, s.head_dim), s.theta)
    v = mm(z, w["wv"]).reshape(n, s.kv_heads, s.head_dim)
    g = s.heads // s.kv_heads  # query head h reads kv head h // g
    k = jnp.repeat(k, g, axis=1)
    v = jnp.repeat(v, g, axis=1)
    sc = jnp.einsum("qhd,khd->hqk", q, k, precision=R.HI) / np.sqrt(s.head_dim)
    causal = jnp.arange(n)[:, None] >= jnp.arange(n)[None, :]
    sc = jnp.where(causal[None], sc, -jnp.inf)
    p = jax.nn.softmax(sc, axis=-1)
    o = jnp.einsum("hqk,khd->qhd", p, v, precision=R.HI).reshape(n, s.q_width)
    x = x + mm(o, w["wo"])
    z = R.rms(x, s.eps)
    return x + mm(jax.nn.silu(mm(z, w["wg"])) * mm(z, w["wi"]), w["wd"])


@functools.partial(jax.jit, static_argnames=("s", "quant"))
def _block_weights(key, i, s: Shapes, quant: str):
    return R.upcast(layer(s, key, i), quant)


def logits(s: Shapes, seed: int, seqs: Sequence[np.ndarray],
           positions: Sequence[np.ndarray], quant: str = ""
           ) -> List[np.ndarray]:
    """Float32 logits (or, with `quant` one of CONTROLS, a control's) of
    each token sequence at the given positions, (len(positions[j]),
    vocab) each."""
    key = W.seed_key(seed)
    emb = jax.jit(lambda k: _embedding(s, k))(key)
    xs = R.embed(emb["tok"], seqs)
    for i in range(s.layers):
        w = _block_weights(key, i, s, quant)
        xs = [_block(x, w, len(q), s, quant) for x, q in zip(xs, seqs)]
        del w
    return R.head_logits(xs, positions, emb, s.eps)


# -- work counts -------------------------------------------------------------

def layer_matmul_params(s: Shapes) -> int:
    """Weights one token multiplies through in one layer: q, k, v, o and
    the three SwiGLU matrices."""
    return 2 * s.d * s.q_width + 2 * s.d * s.kv_width + 3 * s.d * s.ff


def head_flops(s: Shapes) -> int:
    return 2 * s.d * s.vocab


def attn_flops_per_key(s: Shapes) -> int:
    """q.k and p.v for one query against one key, over every layer."""
    return 4 * s.heads * s.head_dim * s.layers


def kv_bytes_per_key(s: Shapes) -> int:
    """K and V of one cached token, over every layer."""
    return 2 * s.kv_width * costs.KV_BYTES * s.layers


def token_flops(s: Shapes, keys: int, head: bool) -> int:
    """One token's forward pass, attending to `keys` cached tokens (itself
    included); `head` when its logits are read."""
    return (2 * s.layers * layer_matmul_params(s) + attn_flops_per_key(s) * keys
            + (head_flops(s) if head else 0))


def prefill_flops(s: Shapes, n: int) -> int:
    """A causal prefill of n tokens whose last logits are read."""
    return (2 * s.layers * layer_matmul_params(s) * n
            + attn_flops_per_key(s) * n * (n + 1) // 2 + head_flops(s))
