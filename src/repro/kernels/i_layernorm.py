"""Pallas TPU kernel: integer LayerNorm (I-BERT), row-blocked.

Paper Fig. 10 layers 4 & 6 (LayerNorm modules, Kern_29/Kern_32).  Each grid
step holds a (block_rows, H) int8 tile in VMEM plus the int32 gamma/beta
vectors; mean/var/Newton-isqrt run entirely in integer VREG math.  H is the
model hidden size (<= 8192 for all assigned archs: fits VMEM comfortably,
e.g. 8 x 8192 int32 = 256KB working set).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.ibert_ops import LN_NORM_SHIFT, _ISQRT_ITERS

BLOCK_ROWS = 8


def _i_sqrt_block(n: jax.Array) -> jax.Array:
    bits = jnp.ceil(jnp.log2(jnp.maximum(n, 1).astype(jnp.float32) + 1.0))
    x0 = jnp.maximum(jnp.exp2(jnp.ceil(bits / 2.0)).astype(jnp.int32), 1)

    # the converged flag rides as int32: Mosaic cannot carry a vector of
    # bools through a loop (scf.for with an i1 vector fails to legalize)
    def body(_, carry):
        x, done = carry
        nx = (x + n // jnp.maximum(x, 1)) >> 1
        newdone = done | (nx >= x).astype(jnp.int32)
        return jnp.where(newdone != 0, x, nx), newdone

    x, _ = jax.lax.fori_loop(0, _ISQRT_ITERS, body,
                             (x0, jnp.zeros(n.shape, jnp.int32)))
    return jnp.where(n == 0, 0, x)


def _kernel(x_ref, g_ref, b_ref, o_ref):
    q = x_ref[...].astype(jnp.int32)
    h = q.shape[-1]
    mean = jnp.sum(q, axis=-1, keepdims=True) // h
    qc = q - mean
    var = jnp.sum(qc * qc, axis=-1, keepdims=True) // h
    std_s = jnp.maximum(_i_sqrt_block(var << 14), 1)
    norm = (qc * (1 << (LN_NORM_SHIFT + 7))) // std_s
    o_ref[...] = (norm * g_ref[...].astype(jnp.int32)
                  + b_ref[...].astype(jnp.int32))


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def i_layernorm(q8: jax.Array, q_gamma: jax.Array, q_beta: jax.Array,
                *, block_rows: int = BLOCK_ROWS,
                interpret: bool = False) -> jax.Array:
    """q8: (R, H) int8-range values; q_gamma/q_beta: (H,) int32. -> (R,H) int32."""
    r, h = q8.shape
    assert r % block_rows == 0, (r, block_rows)
    return pl.pallas_call(
        _kernel,
        grid=(r // block_rows,),
        in_specs=[
            pl.BlockSpec((block_rows, h), lambda i: (i, 0)),
            pl.BlockSpec((1, h), lambda i: (0, 0)),
            pl.BlockSpec((1, h), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_rows, h), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((r, h), jnp.int32),
        interpret=interpret,
    )(q8, q_gamma.reshape(1, h), q_beta.reshape(1, h))
