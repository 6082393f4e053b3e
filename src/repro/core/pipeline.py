"""Pipeline parallelism over clusters (paper §7.2 + §8 Eq. 1 runtime).

The paper deploys I-BERT as one encoder per 6-FPGA cluster, clusters chained
serially in dataflow; the full-model latency follows Eq. 1
``T + (L-1)(X+d)``.  TPU mapping: stage = cluster, the stage axis is a mesh
axis (`pod` for the multi-pod plan, or a dedicated `stage` axis), microbatches
stream GPipe-style and move between stages with collective_permute — the
SPMD form of the paper's gateway-to-gateway inter-cluster messages.

Implemented inside shard_map: stage s holds its slice of the stacked stage
parameters; step t processes microbatch (t - s) and ppermutes activations
forward.  Total steps = n_micro + n_stages - 1, i.e. Eq. 1 with X = T_stage.
"""
from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P


def pipeline_steps(n_micro: int, n_stages: int) -> int:
    return n_micro + n_stages - 1


def gpipe_forward_perm(n_stages: int):
    """The forward collective_permute pairs of the GPipe schedule — stage s
    hands its activation to s+1 (the last stage's wrap-around carries
    garbage that no active stage ever reads).  Shared by `pipelined_apply`
    (training/prefill microbatches) and the serving executor's pipelined
    decode program (decode micro-steps), so the schedule can't drift
    between the two."""
    return [(i, (i + 1) % n_stages) for i in range(n_stages)]


def pipelined_apply(stage_fn: Callable[[Any, jax.Array], jax.Array],
                    mesh: Mesh, axis: str,
                    stage_params: Any, x_micro: jax.Array) -> jax.Array:
    """Run x through n_stages stage_fns, GPipe-schedule over `axis`.

    stage_params: pytree with leading dim n_stages (sharded over `axis`).
    x_micro: (n_micro, mb, ...) microbatched input (replicated over `axis`).
    Returns (n_micro, mb, ...) outputs of the final stage.
    """
    n_stages = mesh.shape[axis]
    n_micro = x_micro.shape[0]
    steps = pipeline_steps(n_micro, n_stages)
    fwd = gpipe_forward_perm(n_stages)

    def body(params, xs):
        # inside shard_map: params leaves have leading dim 1 (this stage)
        params = jax.tree.map(lambda p: p[0], params)
        sidx = lax.axis_index(axis)
        buf = jnp.zeros_like(xs[0])  # activation in flight
        out = jnp.zeros_like(xs)

        def step(t, carry):
            buf, out = carry
            # stage 0 ingests microbatch t; others take the permuted buffer
            x_in = jnp.where(sidx == 0,
                             xs[jnp.minimum(t, n_micro - 1)], buf)
            active = (sidx <= t) & (t - sidx < n_micro)
            y = stage_fn(params, x_in)
            y = jnp.where(active, y, buf)
            # last stage writes its result to output slot t-(n_stages-1)
            oslot = t - (n_stages - 1)
            write = (sidx == n_stages - 1) & (oslot >= 0)
            out = lax.cond(
                write,
                lambda o: lax.dynamic_update_index_in_dim(
                    o, y, jnp.maximum(oslot, 0), 0),
                lambda o: o, out)
            buf = lax.ppermute(y, axis, fwd)
            return buf, out

        _, out = lax.fori_loop(0, steps, step, (buf, out))
        # results live on the last stage; share them with every stage
        out = lax.psum(jnp.where(sidx == n_stages - 1, out, 0.0), axis)
        return out

    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(axis), P()),
        out_specs=P(),
        check_vma=False,
    )(stage_params, x_micro)
