"""Generic decoder backbone covering all 10 assigned architectures.

Layers are grouped into *periods* (the repeating block pattern: 1 for dense,
2 for interleaved MoE, 3 for Griffin's rglru/rglru/attn, 8 for xLSTM's 7:1)
and scanned with stacked parameters, so HLO size is O(period), not O(L) —
essential to keep 512-device SPMD compiles tractable (DESIGN.md §3).  The
remainder layers (e.g. recurrentgemma's 26 = 8*3 + 2) are unrolled as a tail.

Leading dense layers (`cfg.first_k_dense`, DeepSeek-V3's
`first_k_dense_replace`) sit before the scan, unrolled, each with its own
params and cache entry under "lead"; the scan covers the layers after
them.  Trees of configs without them have no "lead" key.

The same block code serves train (no state), prefill (state in/out), and
decode (single-token state update), switched by the cache pytree.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import attention as attn_mod
from repro.models import recurrent as rec_mod
from repro.models.layers import (
    COMPUTE_DTYPE, cross_entropy_chunked, embed, embed_init, lm_head,
    mlp, mlp_init, norm, norm_init,
)
from repro.models.moe import MOE_COUNTERS, moe_ffn, moe_init, moe_share

Params = Dict[str, Any]


# ---------------------------------------------------------------------------
# layer plan
# ---------------------------------------------------------------------------


def period_length(cfg: ModelConfig) -> int:
    if cfg.family == "hybrid" and cfg.block_pattern:
        return len(cfg.block_pattern)
    if cfg.family == "ssm" and cfg.slstm_every:
        return cfg.slstm_every
    if cfg.n_experts and cfg.moe_every > 1:
        return cfg.moe_every
    return 1


def layer_plan(cfg: ModelConfig) -> Tuple[int, int, List[str]]:
    """(n_repeat, tail_len, kinds-of-one-period) of the layers after the
    cfg.first_k_dense leading dense ones."""
    p = period_length(cfg)
    kinds = [cfg.block_kind(i) for i in range(p)]
    n = cfg.n_layers - cfg.first_k_dense
    return n // p, n % p, kinds


LAYER_KEYS = ("lead", "scan", "tail")  # the subtrees that hold layers


def _layers(tree) -> Dict[str, Any]:
    return {k: tree[k] for k in LAYER_KEYS if k in tree}


def _counts0(cfg: ModelConfig):
    """The expert share's routing counters (models/moe.MOE_COUNTERS)
    before any layer adds to them; None for a model without a share."""
    return (jnp.zeros((len(MOE_COUNTERS),), jnp.int32) if cfg.experts_held
            else None)


def _add(acc, c):
    return acc if c is None else acc + c


# ---------------------------------------------------------------------------
# one block
# ---------------------------------------------------------------------------


def block_init(key, cfg: ModelConfig, pos_in_period: int,
               lead: bool = False) -> Params:
    """lead: a leading dense layer (SwiGLU of width cfg.dense_d_ff)."""
    kind = cfg.block_kind(pos_in_period)
    ks = jax.random.split(key, 3)
    p: Params = {"norm1": norm_init(cfg)}
    if kind == "attn":
        p["mix"] = attn_mod.attn_init(ks[0], cfg)
    elif kind == "rglru":
        p["mix"] = rec_mod.rglru_init(ks[0], cfg)
    elif kind == "mlstm":
        p["mix"] = rec_mod.mlstm_init(ks[0], cfg)
    elif kind == "slstm":
        p["mix"] = rec_mod.slstm_init(ks[0], cfg)
    else:
        raise ValueError(kind)
    if cfg.family != "ssm":
        p["norm2"] = norm_init(cfg)
        if lead:
            p["ffn"] = mlp_init(ks[1], cfg, d_ff=cfg.dense_d_ff)
        elif cfg.is_moe_layer(pos_in_period):
            p["ffn"] = moe_init(ks[1], cfg)
        else:
            p["ffn"] = mlp_init(ks[1], cfg)
    return p


def block_apply(cfg: ModelConfig, pos_in_period: int, p: Params, h: jax.Array,
                positions: jax.Array, segment_ids, state,
                pos_contiguous: bool = False, page_table=None, active=None,
                lead: bool = False):
    """Returns (h, new_state, aux_loss, counts): counts are an expert
    share's int32 routing counters (models/moe.MOE_COUNTERS), None for
    every other block."""
    kind = cfg.block_kind(pos_in_period)
    z = norm(h, p["norm1"], cfg)
    if kind == "attn":
        y, new_state = attn_mod.attention(z, p["mix"], cfg, positions,
                                          segment_ids, cache=state,
                                          pos_contiguous=pos_contiguous,
                                          page_table=page_table,
                                          active=active)
    else:
        # pads (pos sentinel 2^30 or segment -1) must not touch the state
        valid = positions < 2**29
        if segment_ids is not None:
            valid &= segment_ids >= 0
        if kind == "rglru":
            y, new_state = rec_mod.rglru_block(z, p["mix"], cfg, state, valid)
        elif kind == "mlstm":
            y, new_state = rec_mod.mlstm_block(z, p["mix"], cfg, state, valid)
        else:  # slstm
            y, new_state = rec_mod.slstm_block(z, p["mix"], cfg, state, valid)
    h = h + y
    aux, counts = jnp.float32(0.0), None
    if "ffn" in p:
        z = norm(h, p["norm2"], cfg)
        if lead:
            y = mlp(z, p["ffn"], cfg)
        elif cfg.is_moe_layer(pos_in_period) and cfg.experts_held:
            valid = positions < 2**29  # not a pad
            if active is not None:
                valid &= active.astype(bool)[:, None]
            y, counts = moe_share(z, p["ffn"], cfg, valid)
        elif cfg.is_moe_layer(pos_in_period):
            y, aux = moe_ffn(z, p["ffn"], cfg)
        else:
            y = mlp(z, p["ffn"], cfg)
        h = h + y
    return h, new_state, aux, counts


def block_init_state(cfg: ModelConfig, pos_in_period: int, batch: int,
                     seq_len: int):
    kind = cfg.block_kind(pos_in_period)
    if kind == "attn":
        return attn_mod.init_attn_cache(cfg, batch, seq_len)
    if kind == "rglru":
        return rec_mod.init_rglru_state(cfg, batch)
    if kind == "mlstm":
        return rec_mod.init_mlstm_state(cfg, batch)
    return rec_mod.init_slstm_state(cfg, batch)


# ---------------------------------------------------------------------------
# cache pytree utilities (slot-table serving)
# ---------------------------------------------------------------------------


def cache_map(fn, *trees):
    """Map ``fn(batch_axis, *leaves)`` over cache pytrees.

    Cache trees are ``{"scan": ..., "tail": ..., "pos": ...}``; leaves under
    "scan" carry a leading stacked-period dim, so their batch axis is 1,
    everything else has batch at axis 0.  Used by the serving engine's
    per-slot insert/select operations, which must address the batch dim.
    """

    def go(axis, *subs):
        if isinstance(subs[0], dict):
            return {k: go(axis, *[s[k] for s in subs]) for k in subs[0]}
        return fn(axis, *subs)

    return {k: go(1 if k == "scan" else 0, *[t[k] for t in trees])
            for k in trees[0]}


def _batch_broadcast(mask: jax.Array, axis: int, ndim: int):
    """(B,) mask -> shape broadcastable against a leaf with batch at `axis`."""
    shape = [1] * ndim
    shape[axis] = mask.shape[0]
    return mask.reshape(shape)


def cache_is_quantized(tree) -> bool:
    """Does this (sub)tree hold int8 KV arenas (k_scale planes present)?"""
    if isinstance(tree, dict):
        if "k_scale" in tree:
            return True
        return any(cache_is_quantized(v) for v in tree.values())
    return False


def quantize_kv_tree(tree):
    """Convert a dense bf16 cache pytree's attention leaves
    ({"k","v","kpos"}) to the quantized arena leaf layout
    ({"k": int8, "v": int8, "k_scale", "v_scale", "kpos"}).

    Quantization is per cache row per kv head over head_dim
    (core/quant.kv_quantize), so it is layout-agnostic: the same rule the
    decode scatter applies token-by-token, applied here to a whole prefill
    bucket at once — which is what keeps prefix-hit suffix ingest
    bit-identical to cold prefill under int8 KV too.
    """
    from repro.core.quant import kv_quantize

    def go(t):
        if isinstance(t, dict):
            if "k" in t and "kpos" in t and "k_scale" not in t:
                kq, ks = kv_quantize(t["k"])
                vq, vs = kv_quantize(t["v"])
                return {"k": kq, "v": vq, "k_scale": ks, "v_scale": vs,
                        "kpos": t["kpos"]}
            return {k: go(v) for k, v in t.items()}
        return t

    return go(tree)


def paged_cache_map(fn, *trees):
    """Map ``fn(page_axis, leaf_name, *leaves)`` over the layer arena
    leaves of paged cache pytrees ("lead", "scan", "tail").

    Arena leaves ((P, ps, ...) under "lead" and "tail", (n_rep, P, ps,
    ...) under "scan") have a *page* axis where dense slot caches have a
    batch axis; top-level "pos"/"pt" are per-lane and handled by the
    caller.
    """

    def go(axis, name, *subs):
        if isinstance(subs[0], dict):
            return {k: go(axis, k, *[s[k] for s in subs]) for k in subs[0]}
        return fn(axis, name, *subs)

    return {k: (go(1 if k == "scan" else 0, k, *[t[k] for t in trees])
                if k in LAYER_KEYS else trees[0][k])
            for k in trees[0]}


def spec_acceptance(ins, tgt, active, remaining, eos_id, pad_token,
                    forced, forced_len, forced_ptr):
    """Vectorized acceptance state machine for one speculative block.

    ins: (B, T) the block's input tokens (T = k+1; column 0 is the lane's
    current input, later columns are forced-queue tokens or draft
    proposals); tgt: (B, T) the target's greedy argmax at each position.
    Mirrors `decode_steps`' forced-queue semantics step for step: step j's
    output is swallowed while ``forced_ptr + j < forced_len``; a *drafted*
    input (one the forced queue didn't cover) is only consumed if it equals
    the target's argmax at the previous step; the first divergence emits
    the target's own argmax — which is already ``tgt[:, j-1]``, the token
    whose emission preceded the divergence — so rejection costs nothing and
    the emitted stream is bit-identical to non-speculative greedy decode.

    Returns (emit (T, B) with -1 holes, cur, alive, remaining, forced_ptr,
    n_consumed) — `n_consumed` is how many block positions the equivalent
    sequential execution would have run, i.e. the position-counter advance
    for both the target and draft caches.
    """
    b, t = ins.shape
    lane = jnp.arange(b)
    fcap = forced.shape[1]
    valid = active   # step 0's input is the lane's own cur: always matched
    alive = active
    rem = remaining
    n_consumed = jnp.zeros((b,), jnp.int32)
    emits = []
    for j in range(t):
        if j > 0:
            drafted = (forced_ptr + j - 1) >= forced_len
            matched = ~drafted | (ins[:, j] == tgt[:, j - 1])
            valid = valid & matched & alive
        n_consumed = n_consumed + valid.astype(jnp.int32)
        swallowed = (forced_ptr + j) < forced_len
        emitting = valid & ~swallowed
        emits.append(jnp.where(emitting, tgt[:, j], -1))
        rem = jnp.where(emitting, rem - 1, rem)
        exited = emitting & ((tgt[:, j] == eos_id) | (rem <= 0))
        alive = alive & ~exited
    # next input for the sequential-equivalent state: the first unconsumed
    # step's input — a still-pending forced token, or the target argmax of
    # the last consumed step (the correction token on divergence, the bonus
    # token on full acceptance; both were just emitted)
    idx = forced_ptr + n_consumed - 1
    from_forced = idx < forced_len
    nxt = jnp.where(
        from_forced,
        forced[lane, jnp.clip(idx, 0, fcap - 1)],
        tgt[lane, jnp.clip(n_consumed - 1, 0, t - 1)]).astype(jnp.int32)
    cur = jnp.where(alive, nxt, pad_token).astype(jnp.int32)
    fptr = forced_ptr + jnp.minimum(
        jnp.maximum(forced_len - forced_ptr, 0), n_consumed)
    return (jnp.stack(emits, axis=0), cur, alive, rem, fptr.astype(jnp.int32),
            n_consumed)


def greedy_token_update(logits, cur, active, remaining, eos_id, pad_token):
    """One step of the fused decode loop's token state machine (no forced
    queue): greedy argmax, -1 emission for masked lanes, EOS/budget lane
    exit, pad feedback.  Shared verbatim by `Model.decode_steps` and the
    serving executor's pipelined decode program, so the two are
    bit-identical by construction.  Returns (emit, cur, active, remaining).
    """
    nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    emit = jnp.where(active, nxt, -1)
    remaining = jnp.where(active, remaining - 1, remaining)
    still = active & (nxt != eos_id) & (remaining > 0)
    # finished/free lanes feed the pad token, never a stale sample
    cur = jnp.where(still, nxt, pad_token).astype(jnp.int32)
    return emit, cur, still, remaining


# ---------------------------------------------------------------------------
# full model
# ---------------------------------------------------------------------------


def init_params(cfg: ModelConfig, key: jax.Array) -> Params:
    n_rep, tail, kinds = layer_plan(cfg)
    keys = jax.random.split(key, n_rep + tail + 2)

    def one_period(k):
        ks = jax.random.split(k, len(kinds))
        return {f"b{i}": block_init(ks[i], cfg, i) for i in range(len(kinds))}

    periods = [one_period(keys[i]) for i in range(n_rep)]
    scan_params = jax.tree.map(lambda *xs: jnp.stack(xs), *periods) if n_rep \
        else {}
    tail_params = {
        str(t): block_init(keys[n_rep + t], cfg, t) for t in range(tail)
    }
    out = {
        "embed": embed_init(keys[-2], cfg),
        "scan": scan_params,
        "tail": tail_params,
        "final_norm": norm_init(cfg),
    }
    if cfg.first_k_dense:
        lk = jax.random.split(jax.random.fold_in(key, 1 << 20),
                              cfg.first_k_dense)
        out["lead"] = {str(i): block_init(lk[i], cfg, 0, lead=True)
                       for i in range(cfg.first_k_dense)}
    return out


class Model:
    """Thin functional wrapper binding a ModelConfig to apply functions."""

    def __init__(self, cfg: ModelConfig, remat: bool = True):
        self.cfg = cfg
        self.remat = remat

    # -- core --------------------------------------------------------------

    def backbone(self, params: Params, h: jax.Array, positions: jax.Array,
                 segment_ids=None, caches=None, pos_contiguous: bool = False,
                 page_table=None, active=None):
        """h: (B,S,D) embeddings -> (h_final, new_caches, aux, counts):
        counts sums the expert share's routing counters over the layers
        (None without a share).

        pos_contiguous: positions are a plain broadcast arange (no pad
        sentinels) — lets long-prefill attention take the Pallas fused path.
        page_table/active: paged-KV decode (models/attention.py) — the
        table is shared by every layer, so it rides alongside positions
        instead of being stacked into the per-layer cache pytree.
        """
        cfg = self.cfg
        n_rep, tail, kinds = layer_plan(cfg)
        np_ = len(kinds)

        new_lead = {}
        for i in range(cfg.first_k_dense):  # dense MLPs: no aux, no counts
            st = None if caches is None else caches["lead"][str(i)]
            h, new_lead[str(i)], _, _ = block_apply(
                cfg, 0, params["lead"][str(i)], h, positions, segment_ids,
                st, pos_contiguous=pos_contiguous, page_table=page_table,
                active=active, lead=True)

        def period_fn(h, period_params, period_caches, layer=None):
            new_caches = {}
            aux, counts = jnp.float32(0.0), _counts0(cfg)
            for i in range(np_):
                st = None if period_caches is None else period_caches[f"b{i}"]
                if layer is not None:  # stacked arenas, this layer's index
                    st = dict(st, layer=layer)
                h, ns, a, c = block_apply(cfg, i, period_params[f"b{i}"], h,
                                          positions, segment_ids, st,
                                          pos_contiguous=pos_contiguous,
                                          page_table=page_table,
                                          active=active)
                if period_caches is not None:
                    new_caches[f"b{i}"] = ns
                aux, counts = aux + a, _add(counts, c)
            return h, new_caches, aux, counts

        pf = period_fn
        if self.remat and caches is None:
            pf = jax.checkpoint(
                period_fn,
                policy=jax.checkpoint_policies.nothing_saveable,
            )

        if n_rep and caches is not None and page_table is not None \
                and cfg.kv_lora_rank:
            # latent decode: the stacked arenas ride the carry and each
            # layer writes its rows into, and attends over, its own pages
            # in place (models/attention.mla_attention); as scanned xs/ys
            # they would be sliced out and written back a layer at a time,
            # and the ys would be a second whole arena
            def carry_body(carry, pp):
                h, aux_acc, cnt_acc, arenas, li = carry
                h, arenas, aux, cnt = pf(h, pp, arenas, li)
                return (h, aux_acc + aux, _add(cnt_acc, cnt), arenas,
                        li + 1), None

            (h, aux, counts, new_scan_caches, _), _ = jax.lax.scan(
                carry_body, (h, jnp.float32(0.0), _counts0(cfg),
                             caches["scan"], jnp.int32(0)), params["scan"])
        elif n_rep:
            def scan_body(carry, xs):
                h, aux_acc, cnt_acc = carry
                pp = xs[0] if caches is not None else xs
                pc = xs[1] if caches is not None else None
                h, ncache, aux, cnt = pf(h, pp, pc)
                return (h, aux_acc + aux, _add(cnt_acc, cnt)), (
                    ncache if caches is not None else None)

            xs = (params["scan"], caches["scan"]) if caches is not None \
                else params["scan"]
            (h, aux, counts), new_scan_caches = jax.lax.scan(
                scan_body, (h, jnp.float32(0.0), _counts0(cfg)), xs)
        else:
            aux, counts = jnp.float32(0.0), _counts0(cfg)
            new_scan_caches = None

        new_tail = {}
        for t in range(tail):
            st = None if caches is None else caches["tail"][str(t)]
            h, ns, a, c = block_apply(cfg, t, params["tail"][str(t)], h,
                                      positions, segment_ids, st,
                                      pos_contiguous=pos_contiguous,
                                      page_table=page_table, active=active)
            if caches is not None:
                new_tail[str(t)] = ns
            aux, counts = aux + a, _add(counts, c)

        h = norm(h, params["final_norm"], cfg)
        new_caches = (None if caches is None else
                      {"scan": new_scan_caches, "tail": new_tail})
        if caches is not None and cfg.first_k_dense:
            new_caches["lead"] = new_lead
        return h, new_caches, aux, counts

    def embed_inputs(self, params, tokens=None, embeds=None):
        if embeds is not None:
            return embeds.astype(COMPUTE_DTYPE)
        return embed(tokens, params["embed"])

    # -- entry points --------------------------------------------------------

    def loss(self, params: Params, batch: Dict[str, jax.Array]) -> jax.Array:
        """batch: tokens|embeds, labels, positions?, segment_ids?"""
        tokens = batch.get("tokens")
        embeds = batch.get("embeds")
        x = self.embed_inputs(params, tokens, embeds)
        b, s = x.shape[:2]
        positions = batch.get("positions")
        contiguous = positions is None
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
        h, _, aux, _ = self.backbone(params, x, positions,
                                     batch.get("segment_ids"),
                                     pos_contiguous=contiguous)
        ce = cross_entropy_chunked(h, batch["labels"], params["embed"])
        return ce + 0.01 * aux

    def forward_logits(self, params, tokens=None, embeds=None, positions=None):
        x = self.embed_inputs(params, tokens, embeds)
        b, s = x.shape[:2]
        contiguous = positions is None
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
        h, _, _, _ = self.backbone(params, x, positions,
                                   pos_contiguous=contiguous)
        return lm_head(h, params["embed"])

    def init_cache(self, batch: int, seq_len: int):
        cfg = self.cfg
        n_rep, tail, kinds = layer_plan(cfg)

        def one_period():
            return {f"b{i}": block_init_state(cfg, i, batch, seq_len)
                    for i in range(len(kinds))}

        scan_caches = jax.tree.map(
            lambda x: jnp.broadcast_to(x, (n_rep,) + x.shape), one_period()
        ) if n_rep else {}
        tail_caches = {str(t): block_init_state(cfg, t, batch, seq_len)
                       for t in range(tail)}
        out = {"scan": scan_caches, "tail": tail_caches,
               "pos": jnp.zeros((batch,), jnp.int32)}
        if cfg.first_k_dense:
            out["lead"] = {str(i): block_init_state(cfg, 0, batch, seq_len)
                           for i in range(cfg.first_k_dense)}
        return out

    def init_paged_cache(self, batch: int, num_pages: int, page_size: int,
                         max_pages: int, kv_dtype: str = "bf16"):
        """Paged serving cache: global KV page arenas + per-lane tables.

        Tree: {"scan"/"tail": per-layer {"k","v","kpos"} arenas with no
        batch axis (models/attention.init_paged_attn_cache), "pos": (B,)
        position counters, "pt": (B, max_pages) int32 page tables (0 = the
        allocator's reserved trash page)}.  Only all-attention configs
        qualify — recurrent state has no paged analogue, and ring-buffer
        (windowed) caches stay on the dense slot path.

        kv_dtype="int8" stores quantized arenas (int8 k/v + f32
        `k_scale`/`v_scale` planes): ~half the HBM per cache row, so an
        equal byte budget holds ~2x the pages (docs/perf.md §int8 pages).
        """
        cfg = self.cfg
        n_rep, tail, kinds = layer_plan(cfg)
        bad = [k for k in kinds if k != "attn"]
        assert not bad, f"paged KV needs an all-attention model, got {bad}"
        assert kv_dtype in ("bf16", "int8"), kv_dtype
        quant = kv_dtype == "int8"

        def one_period():
            return {f"b{i}": attn_mod.init_paged_attn_cache(
                cfg, num_pages, page_size, quantized=quant)
                for i in range(len(kinds))}

        scan_caches = jax.tree.map(
            lambda x: jnp.broadcast_to(x, (n_rep,) + x.shape), one_period()
        ) if n_rep else {}
        tail_caches = {str(t): attn_mod.init_paged_attn_cache(
            cfg, num_pages, page_size, quantized=quant)
            for t in range(tail)}
        out = {"scan": scan_caches, "tail": tail_caches,
               "pos": jnp.zeros((batch,), jnp.int32),
               "pt": jnp.zeros((batch, max_pages), jnp.int32)}
        if cfg.first_k_dense:
            out["lead"] = {str(i): attn_mod.init_paged_attn_cache(
                cfg, num_pages, page_size, quantized=quant)
                for i in range(cfg.first_k_dense)}
        if cfg.experts_held:  # the decode program's routing counters
            out["moe"] = jnp.zeros((3,), jnp.int32)
        return out

    def prefill(self, params, caches, tokens=None, embeds=None,
                positions=None, last_idx=None):
        """Fill caches from a (left-aligned) prompt.

        last_idx: (B,) index of each request's final prompt token (for
        padded batches of unequal lengths); defaults to S-1.
        Returns (logits at last_idx, caches).
        """
        x = self.embed_inputs(params, tokens, embeds)
        b, s = x.shape[:2]
        contiguous = positions is None
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
        if last_idx is None:
            last_idx = jnp.full((b,), s - 1, jnp.int32)
        h, sub, _, _ = self.backbone(params, x, positions,
                                     caches=_layers(caches),
                                     pos_contiguous=contiguous)
        bidx = jnp.arange(b)
        last_pos = positions[bidx, last_idx].astype(jnp.int32)
        caches = dict(sub, pos=last_pos + 1)
        h_last = h[bidx, last_idx][:, None]
        return lm_head(h_last, params["embed"])[:, 0], caches

    def decode_step(self, params, caches, token: jax.Array,
                    active: Optional[jax.Array] = None):
        """token: (B,) int32 (or (B,D) embeds for stub frontends).

        active: optional (B,) bool slot mask (continuous batching).  Rows
        with ``active=False`` are computed (the batch shape is static) but
        their cache entries and position counters are left untouched, so a
        free/finished slot cannot corrupt its state between an occupant
        finishing and the next admission overwriting the slot.
        """
        if token.ndim == 1:
            x = self.embed_inputs(params, tokens=token[:, None])
        else:
            x = token[:, None, :].astype(COMPUTE_DTYPE)
        positions = caches["pos"][:, None]
        sub = _layers(caches)
        if "pt" in caches:
            # paged: writes are already active-gated inside attention (an
            # arena has no batch axis for cache_map's where-masking), so
            # only the per-lane position counters need the mask here
            h, sub, _, counts = self.backbone(params, x, positions,
                                              caches=sub,
                                              page_table=caches["pt"],
                                              active=active)
            pos = caches["pos"] + 1 if active is None else jnp.where(
                active, caches["pos"] + 1, caches["pos"])
            out = dict(sub, pos=pos, pt=caches["pt"])
            if "moe" in caches:  # the expert share's routing counters
                out["moe"] = caches["moe"] + counts
            return lm_head(h[:, -1:], params["embed"])[:, 0], out
        h, sub, _, _ = self.backbone(params, x, positions, caches=sub)
        new_caches = dict(sub, pos=caches["pos"] + 1)
        if active is not None:
            new_caches = cache_map(
                lambda ax, new, old: jnp.where(
                    _batch_broadcast(active, ax, new.ndim), new, old),
                new_caches, caches)
        return lm_head(h[:, -1:], params["embed"])[:, 0], new_caches

    def decode_steps(self, params, caches, token: jax.Array,
                     active: jax.Array, n: int,
                     eos_id: Optional[jax.Array] = None,
                     budget: Optional[jax.Array] = None,
                     pad_token: int = 0,
                     forced: Optional[jax.Array] = None,
                     forced_len: Optional[jax.Array] = None,
                     forced_ptr: Optional[jax.Array] = None):
        """n fused greedy decode steps as one on-device ``lax.scan``.

        The serving fast path: instead of one jit dispatch + one (B, V)
        logits fetch + a host argmax per generated token, the whole hot loop
        (decode_step -> greedy argmax -> feed back) runs on the accelerator
        and the host fetches a single (n, B) int32 token block per dispatch.

        token: (B,) int32 current input tokens; active: (B,) bool slot mask;
        eos_id: optional (B,) int32 per-slot EOS (-1 = never); budget:
        optional (B,) int32 tokens each slot may still emit.  A slot
        early-exits on device — its ``active`` lane drops after it emits EOS
        or exhausts its budget, and from then on it emits -1 and (like any
        inactive slot) leaves its cache rows and position counter untouched,
        so the token streams are bit-identical to n chained ``decode_step``
        calls reconciled on the host.

        forced/forced_len/forced_ptr (all or none): per-lane queues of
        *forced* input tokens — the prefix-cache hit path's prompt-suffix
        ingest.  While ``forced_ptr[b] < forced_len[b]`` the lane feeds
        ``forced[b, forced_ptr[b]]`` as the next input instead of its own
        argmax, emits -1 (nothing generated yet), and leaves its budget and
        EOS state untouched; the step that consumes the lane's last pending
        input emits the first generated token.  This is chunked prefill
        riding the decode loop: the forced tokens' KV lands at the right
        positions and the resulting stream is bit-identical to a cold
        prefill of the full prompt.

        Returns (tokens (n, B) int32 with -1 for inactive lanes, next token
        (B,), active (B,), remaining budget (B,), caches) — with a forced
        queue, the advanced forced_ptr (B,) is inserted before caches.
        """
        b = token.shape[0]
        if eos_id is None:
            eos_id = jnp.full((b,), -1, jnp.int32)
        if budget is None:
            budget = jnp.full((b,), 2 ** 30, jnp.int32)
        if "moe" in caches:  # counters count this dispatch's steps
            caches = dict(caches, moe=jnp.zeros_like(caches["moe"]))

        if forced is None:

            def step(carry, _):
                cur, act, rem, caches = carry
                logits, caches = self.decode_step(params, caches, cur,
                                                  active=act)
                emit, cur, still, rem = greedy_token_update(
                    logits, cur, act, rem, eos_id, pad_token)
                return (cur, still, rem, caches), emit

            (cur, act, rem, caches), toks = jax.lax.scan(
                step, (token.astype(jnp.int32), active, budget, caches),
                None, length=n)
            return toks, cur, act, rem, caches

        fcap = forced.shape[1]
        lane = jnp.arange(b)

        def step(carry, _):
            cur, act, rem, fptr, caches = carry
            logits, caches = self.decode_step(params, caches, cur,
                                              active=act)
            nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            pending = fptr < forced_len  # this step's output is swallowed
            emitting = act & ~pending
            emit = jnp.where(emitting, nxt, -1)
            rem = jnp.where(emitting, rem - 1, rem)
            still = act & (pending | ((nxt != eos_id) & (rem > 0)))
            feed = jnp.where(
                pending, forced[lane, jnp.minimum(fptr, fcap - 1)], nxt)
            cur = jnp.where(still, feed, pad_token).astype(jnp.int32)
            fptr = jnp.where(act & pending, fptr + 1, fptr)
            return (cur, still, rem, fptr, caches), emit

        (cur, act, rem, fptr, caches), toks = jax.lax.scan(
            step, (token.astype(jnp.int32), active, budget,
                   forced_ptr.astype(jnp.int32), caches), None, length=n)
        return toks, cur, act, rem, fptr, caches

    def draft_steps(self, params, caches, token: jax.Array,
                    active: jax.Array, n_draft: int,
                    forced: jax.Array, forced_len: jax.Array,
                    forced_ptr: jax.Array, pad_token: int = 0):
        """Build one speculative input block on the draft model.

        Runs ``n_draft + 1`` draft decode steps: step j consumes input
        ``ins[:, j]`` — the forced-queue token when the queue still covers
        the position (so a prefix-hit lane's draft cache ingests the same
        suffix stream the target does), the previous step's draft argmax
        otherwise.  The final step exists only to ingest the last input's
        KV, so the draft cache covers every position the target will
        verify; its output is discarded.  Returns (ins (B, n_draft+1),
        caches) with the draft position counters advanced by n_draft+1 —
        the caller rewinds them to the accepted length.
        """
        b = token.shape[0]
        lane = jnp.arange(b)
        fcap = forced.shape[1]
        cur = jnp.where(active, token, pad_token).astype(jnp.int32)
        ins = [cur]
        for j in range(n_draft + 1):
            logits, caches = self.decode_step(params, caches, cur,
                                              active=active)
            if j == n_draft:
                break
            prop = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            idx = forced_ptr + j
            nxt = jnp.where(idx < forced_len,
                            forced[lane, jnp.minimum(idx, fcap - 1)], prop)
            cur = jnp.where(active, nxt, pad_token).astype(jnp.int32)
            ins.append(cur)
        return jnp.stack(ins, axis=1), caches

    def verify_block(self, params, caches, tokens: jax.Array,
                     active: Optional[jax.Array] = None):
        """Batched target pass over T contiguous speculative inputs.

        tokens: (B, T) — position ``caches['pos'][b] + j`` for column j.
        One backbone call with Sq = T rides the paged multi-query verify
        branch (models/attention.py): every row's logits are bitwise
        identical to what T chained single-step `decode_step` calls with
        the same inputs would produce, which is the whole lossless-greedy
        argument.  Returns (logits (B, T, V), caches) with the position
        counters untouched — the caller advances them by the accepted
        length only.
        """
        assert "pt" in caches, "verify_block requires a paged cache"
        b, t = tokens.shape
        x = self.embed_inputs(params, tokens=tokens)
        positions = (caches["pos"][:, None]
                     + jnp.arange(t, dtype=jnp.int32)[None, :])
        h, sub, _, _ = self.backbone(params, x, positions,
                                     caches=_layers(caches),
                                     page_table=caches["pt"], active=active)
        logits = lm_head(h, params["embed"])
        return logits, dict(caches, **sub)

    def spec_decode_step(self, params, caches, token: jax.Array,
                         active: jax.Array, n_draft: int,
                         draft_model: "Model", draft_params, draft_caches,
                         eos_id: Optional[jax.Array] = None,
                         budget: Optional[jax.Array] = None,
                         pad_token: int = 0,
                         forced: Optional[jax.Array] = None,
                         forced_len: Optional[jax.Array] = None,
                         forced_ptr: Optional[jax.Array] = None):
        """One fused speculative block: draft scan + batched target verify
        + acceptance ingest, emitting up to ``n_draft + 1`` tokens per lane
        per dispatch while staying bit-identical to `decode_steps`.

        Rejection rollback is a position-counter rewind on both caches:
        rejected rows sit at kpos beyond every future query position until
        the sequential stream overwrites them (write-then-attend plus the
        causal mask make them unreachable — docs/serving.md §speculative
        decoding).  Returns (toks (n_draft+1, B), cur, active, remaining,
        forced_ptr, caches, draft_caches, n_consumed).
        """
        b = token.shape[0]
        if eos_id is None:
            eos_id = jnp.full((b,), -1, jnp.int32)
        if budget is None:
            budget = jnp.full((b,), 2 ** 30, jnp.int32)
        if forced is None:
            forced = jnp.zeros((b, 1), jnp.int32)
            forced_len = jnp.zeros((b,), jnp.int32)
            forced_ptr = jnp.zeros((b,), jnp.int32)
        ins, draft_caches = draft_model.draft_steps(
            draft_params, draft_caches, token, active, n_draft,
            forced, forced_len, forced_ptr, pad_token)
        logits, caches = self.verify_block(params, caches, ins,
                                           active=active)
        tgt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        toks, cur, alive, rem, fptr, v = spec_acceptance(
            ins, tgt, active, budget, eos_id, pad_token,
            forced, forced_len, forced_ptr)
        caches = dict(caches, pos=jnp.where(
            active, caches["pos"] + v, caches["pos"]))
        draft_caches = dict(draft_caches, pos=jnp.where(
            active, draft_caches["pos"] - (n_draft + 1) + v,
            draft_caches["pos"]))
        return toks, cur, alive, rem, fptr, caches, draft_caches, v

    def insert_prefill_cache(self, big, small, slot: jax.Array):
        """Write batch-1 prefill caches `small` into row `slot` of the
        persistent slot table `big` (prefill-on-admission).

        Leaf shapes must match except the batch dim (1 vs max_batch) and,
        optionally, the KV slot dim, which may be shorter in `small` when
        prefill ran with a smaller bucket cache; the gap is refilled with
        zeros (k/v) or the never-written position sentinel (kpos), so stale
        entries from the slot's previous occupant can never be attended to.
        """
        slot = jnp.asarray(slot, jnp.int32)

        def leaf(axis, b, s):
            s = s.astype(b.dtype)
            tgt = b.shape[:axis] + (1,) + b.shape[axis + 1:]
            if s.shape != tgt:
                fill = 2 ** 30 if b.dtype == jnp.int32 else 0  # kpos sentinel
                pad = [(0, t - d) for t, d in zip(tgt, s.shape)]
                s = jnp.pad(s, pad, constant_values=fill)
            return jax.lax.dynamic_update_slice_in_dim(b, s, slot, axis=axis)

        return cache_map(leaf, big, small)

    def admit_lane_cache(self, big, slot: jax.Array, pt_row: jax.Array,
                         pos0: jax.Array, reset_pages: jax.Array,
                         small=None, write_pages=None):
        """Prepare one lane of a *paged* cache `big` for a new occupant.

        reset_pages: (R,) int32 — arena pages whose `kpos` return to the
        never-written sentinel before use (pages are recycled, so a new
        owner must be unable to attend to the previous occupant's keys;
        pad with the trash page 0, which is idempotently sentinel).
        small/write_pages: optional batch-1 bucket cache from a dense
        prefill plus the (W,) pages that receive it — positions
        [0, W*page_size) land at write_pages in prompt order.  The lane's
        page-table row becomes `pt_row` and its position counter `pos0`
        (the prompt length; a prefix-cache hit passes hit_len and no
        `small` — the suffix arrives through the decode loop's forced
        queue instead).  A quantized arena (`kv_dtype="int8"`) quantizes
        the bucket cache on the way in, per cache row, so its leaves match
        the arena's int8 + scale layout.
        """
        slot = jnp.asarray(slot, jnp.int32)
        if small is not None and cache_is_quantized(big):
            small = quantize_kv_tree(small)

        def leaf(page_axis, name, b, s):
            ps = b.shape[page_axis + 1]
            if name == "kpos":
                sent = jnp.full((reset_pages.shape[0], ps) if page_axis == 0
                                else (b.shape[0], reset_pages.shape[0], ps),
                                2 ** 30, b.dtype)
                b = (b.at[reset_pages].set(sent) if page_axis == 0
                     else b.at[:, reset_pages].set(sent))
            if s is None:
                return b
            s = jnp.squeeze(s, axis=page_axis).astype(b.dtype)
            n_wp = write_pages.shape[0]
            need, got = n_wp * ps, s.shape[page_axis]
            if got < need:
                fill = 2 ** 30 if name == "kpos" else 0
                pad = [(0, 0)] * s.ndim
                pad[page_axis] = (0, need - got)
                s = jnp.pad(s, pad, constant_values=fill)
            elif got > need:  # lane owns fewer pages than the bucket spans
                s = jax.lax.slice_in_dim(s, 0, need, axis=page_axis)
            s = s.reshape(s.shape[:page_axis] + (n_wp, ps)
                          + s.shape[page_axis + 1:])
            return (b.at[write_pages].set(s) if page_axis == 0
                    else b.at[:, write_pages].set(s))

        out = paged_cache_map(
            lambda ax, name, bb: leaf(ax, name, bb, None), big) \
            if small is None else paged_cache_map(
                lambda ax, name, bb, ss: leaf(ax, name, bb, ss), big,
                _layers(small))
        out["pos"] = big["pos"].at[slot].set(jnp.asarray(pos0, jnp.int32))
        out["pt"] = big["pt"].at[slot].set(
            jnp.asarray(pt_row, jnp.int32))
        return out


def make_model(cfg: ModelConfig, remat: bool = True) -> Model:
    return Model(cfg, remat=remat)
