"""Model shapes from a configuration file, and random weights from a seed.

Every tensor is drawn from its own key, folded from the seed, the layer
and the tensor's name, so the reference can make layer i alone and get
the very values the served model holds, without taking anything the
program made.  Weights are normal with std ``initializer_range`` and
stored in bfloat16, the type they are served in; norm gains are 1.
"""
from __future__ import annotations

import json
from dataclasses import dataclass
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

SERVE_DTYPE = jnp.bfloat16
# fixed ids, so a tensor's values never depend on which others exist
_IDS = {"wq": 1, "wk": 2, "wv": 3, "wo": 4, "wi": 5, "wg": 6, "wd": 7,
        "tok": 101, "head": 102}


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


def seed_key(seed: int) -> jax.Array:
    """A PRNG key for any whole-number seed."""
    ss = np.random.SeedSequence([abs(int(seed)) % 2 ** 64, int(seed < 0)])
    return jax.random.PRNGKey(int(ss.generate_state(1)[0]))


def _draw(key, name: str, shape, std: float) -> jax.Array:
    k = jax.random.fold_in(key, _IDS[name])
    return (jax.random.normal(k, shape, jnp.float32) * std).astype(SERVE_DTYPE)


def layer(s: Shapes, key: jax.Array, i) -> dict:
    """Layer i's matrices, (in, out) layout, bfloat16."""
    k = jax.random.fold_in(key, i)
    return {"wq": _draw(k, "wq", (s.d, s.q_width), s.std),
            "wk": _draw(k, "wk", (s.d, s.kv_width), s.std),
            "wv": _draw(k, "wv", (s.d, s.kv_width), s.std),
            "wo": _draw(k, "wo", (s.q_width, s.d), s.std),
            "wg": _draw(k, "wg", (s.d, s.ff), s.std),
            "wi": _draw(k, "wi", (s.d, s.ff), s.std),
            "wd": _draw(k, "wd", (s.ff, s.d), s.std)}


def embedding(s: Shapes, key: jax.Array) -> dict:
    """Token table (vocab, d) and, when untied, the head (d, vocab)."""
    k = jax.random.fold_in(key, 1 << 20)
    out = {"tok": _draw(k, "tok", (s.vocab, s.d), s.std)}
    if not s.tied:
        out["head"] = _draw(k, "head", (s.d, s.vocab), s.std)
    return out


def served_params(s: Shapes, seed: int):
    """The served model's parameter tree, made on the device in one jitted
    call (the layer stack one layer at a time, so no whole-stack float32
    copy exists).  The tree is the serving program's layout: the stacked
    layer scan plus embedding and final norm."""

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
        return {"embed": embedding(s, key), "scan": scan, "tail": {},
                "final_norm": {"g": jnp.ones((s.d,), jnp.float32)}}

    return jax.jit(make)(seed_key(seed))
