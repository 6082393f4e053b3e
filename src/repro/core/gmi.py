"""GMI — the Galapagos Messaging Interface (paper §5), on mesh axes.

The paper defines a minimal collective set (Broadcast / Reduce / Scatter /
Gather) plus composition ("Allgather = Gather to a root, then Broadcast",
§5.1) and two communicator levels: intra-cluster and inter-cluster, where
ALL inter-cluster traffic funnels through a Gateway kernel (§4).

TPU mapping (DESIGN.md §2):
  * communicator  = named mesh axis (or tuple of axes) inside shard_map
  * intra-cluster = `data`/`model` axes;  inter-cluster = `pod` axis
  * gateway       = the two-phase hierarchical schedule: intra-pod
    reduce-scatter, inter-pod exchange between same-index shard leaders only,
    intra-pod all-gather.  Each device talks across pods only to its
    same-index peer — the SPMD expression of "kernel 0 forwards everything".

Two implementations are provided for each All* collective:
  * `*_composed` — the paper-faithful root-based composition (C5 baseline)
  * the fused `lax` one-step collective (beyond-paper optimized)
§Perf compares their collective-byte counts from lowered HLO.

All functions must be called inside shard_map (they use lax collectives).
"""
from __future__ import annotations

from typing import Sequence, Union

import jax
import jax.numpy as jnp
from jax import lax

Axis = Union[str, Sequence[str]]


def _index(axis: Axis) -> jax.Array:
    if isinstance(axis, str):
        return lax.axis_index(axis)
    idx = jnp.int32(0)
    for a in axis:
        idx = idx * lax.axis_size(a) + lax.axis_index(a)
    return idx


def axis_size(axis: Axis) -> int:
    if isinstance(axis, str):
        return lax.axis_size(axis)
    n = 1
    for a in axis:
        n *= lax.axis_size(a)
    return n


# -- the four GMI primitives -------------------------------------------------


def broadcast(x: jax.Array, axis: Axis, root: int = 0) -> jax.Array:
    """Every rank receives root's x.  (masked all-reduce: the standard SPMD
    expression of one-to-all; on ICI this lowers to a broadcast tree.)"""
    mask = (_index(axis) == root).astype(x.dtype)
    return lax.psum(x * mask, axis)


def reduce(x: jax.Array, axis: Axis, root: int = 0) -> jax.Array:
    """Sum of x over the group, valid only on root (others get zeros)."""
    r = lax.psum(x, axis)
    mask = (_index(axis) == root).astype(x.dtype)
    return r * mask


def gather(x: jax.Array, axis: Axis, root: int = 0) -> jax.Array:
    """Concatenate group members' x along a new leading dim, on root only."""
    g = lax.all_gather(x, axis, axis=0, tiled=False)
    if not isinstance(axis, str):
        n = axis_size(axis)
        g = g.reshape((n,) + x.shape)
    mask = (_index(axis) == root).astype(x.dtype)
    return g * mask


def scatter(x: jax.Array, axis: Axis, root: int = 0) -> jax.Array:
    """Root holds (group_size, ...); member i receives slice i."""
    full = broadcast(x, axis, root)
    return jnp.take(full, _index(axis), axis=0)


# -- composed All* (paper-faithful: via a root, §5.1) ------------------------


def allgather_composed(x: jax.Array, axis: Axis, root: int = 0) -> jax.Array:
    g = gather(x, axis, root)  # root has all
    return broadcast(g, axis, root)


def allreduce_composed(x: jax.Array, axis: Axis, root: int = 0) -> jax.Array:
    r = reduce(x, axis, root)
    return broadcast(r, axis, root)


# -- fused one-step collectives (optimized) ----------------------------------


def allgather(x: jax.Array, axis: Axis) -> jax.Array:
    g = lax.all_gather(x, axis, axis=0, tiled=False)
    if not isinstance(axis, str):
        g = g.reshape((axis_size(axis),) + x.shape)
    return g


def allreduce(x: jax.Array, axis: Axis) -> jax.Array:
    return lax.psum(x, axis)


def reduce_scatter(x: jax.Array, axis: Axis) -> jax.Array:
    """Sum over group, each member keeps its slice of leading dim."""
    return lax.psum_scatter(x, axis, scatter_dimension=0, tiled=True)


# -- hierarchical (gateway) collectives: the clusters-of-clusters schedule ---


def hier_allreduce(x: jax.Array, intra_axis: Axis, inter_axis: Axis
                   ) -> jax.Array:
    """Gateway-style inter-cluster allreduce (paper §4).

    Phase 1: reduce-scatter inside the cluster (each member becomes the
    gateway for its shard).  Phase 2: the per-shard gateways all-reduce
    across clusters (1/N_intra of the naive inter-cluster bytes).  Phase 3:
    all-gather inside the cluster.
    """
    n = axis_size(intra_axis)
    pad = (-x.shape[0]) % n
    xp = jnp.pad(x, [(0, pad)] + [(0, 0)] * (x.ndim - 1)) if pad else x
    shard = lax.psum_scatter(xp, intra_axis, scatter_dimension=0, tiled=True)
    shard = lax.psum(shard, inter_axis)
    full = lax.all_gather(shard, intra_axis, axis=0, tiled=True)
    return full[: x.shape[0]] if pad else full


def flat_allreduce(x: jax.Array, intra_axis: Axis, inter_axis: Axis
                   ) -> jax.Array:
    """Naive single-phase allreduce over both levels (the DFX-style baseline
    the paper argues against: every kernel talks to every cluster)."""
    axes = ((intra_axis,) if isinstance(intra_axis, str) else tuple(intra_axis))
    axes += (inter_axis,) if isinstance(inter_axis, str) else tuple(inter_axis)
    return lax.psum(x, axes)


def hier_allgather(x: jax.Array, intra_axis: Axis, inter_axis: Axis
                   ) -> jax.Array:
    """Gather within cluster, exchange across clusters via gateways, then
    broadcast within cluster — returns (n_inter, n_intra, ...) stacked."""
    intra = allgather(x, intra_axis)  # (n_intra, ...)
    inter = lax.all_gather(intra, inter_axis, axis=0, tiled=False)
    return inter


# -- point-to-point through the gateway (inter-cluster send, §5.2) -----------


def cluster_send(x: jax.Array, inter_axis: str, dst_offset: int = 1
                 ) -> jax.Array:
    """Send x to the next cluster along the ring (one-byte-header GMI
    inter-cluster message -> collective_permute on the pod axis)."""
    n = lax.axis_size(inter_axis)
    perm = [(i, (i + dst_offset) % n) for i in range(n)]
    return lax.ppermute(x, inter_axis, perm)
