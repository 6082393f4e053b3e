"""Random weights from a seed, drawn the same way for every model family.

Every tensor is drawn from its own key, folded from the seed, the layer
and the tensor's fixed id, so the reference can make layer i alone and get
the very values the served model holds, without taking anything the
program made.  Weights are normal with std ``initializer_range`` and
stored in bfloat16, the type they are served in.  Each family
(families/<model_type>.py) names its layer tensors and their ids; the
token table and the head are drawn here.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

SERVE_DTYPE = jnp.bfloat16
TOK, HEAD = 101, 102  # ids of the token table and the untied head
EMBED_FOLD = 1 << 20  # folded in place of a layer index for the embedding


def seed_key(seed: int) -> jax.Array:
    """A PRNG key for any whole-number seed."""
    ss = np.random.SeedSequence([abs(int(seed)) % 2 ** 64, int(seed < 0)])
    return jax.random.PRNGKey(int(ss.generate_state(1)[0]))


def draw(key, ident: int, shape, std: float) -> jax.Array:
    """The tensor with fixed id `ident` under `key`, in the served type;
    its values never depend on which other tensors exist."""
    k = jax.random.fold_in(key, ident)
    return (jax.random.normal(k, shape, jnp.float32) * std).astype(SERVE_DTYPE)


def embedding(key, vocab: int, d: int, std: float, tied: bool) -> dict:
    """Token table (vocab, d) and, when untied, the head (d, vocab)."""
    k = jax.random.fold_in(key, EMBED_FOLD)
    out = {"tok": draw(k, TOK, (vocab, d), std)}
    if not tied:
        out["head"] = draw(k, HEAD, (d, vocab), std)
    return out
