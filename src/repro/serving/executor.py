"""Device-side serving execution: every jitted program, in one place.

The executor owns params (quantizing them for W8A8 serving when asked),
applies the Cluster-Builder plan (`jax.device_put` placement +
`in_shardings`/`out_shardings` on every program, so donated cache updates
never migrate), and compiles/caches the serving programs: bucketed
prefill, the fused decode-horizon loop (`Model.decode_steps`), the
slot/lane admission updates, and — under a `mode="serve_pipeline"` plan —
the stage-pipelined decode program that streams micro-steps through the
mesh with `collective_permute` (the TPU analogue of the paper's six-FPGA
pipelined encoder).  Each program is jitted from a function of its own
name, so a profile names its module `jit_<name>` (`prefill`, `pfn` for
the paged decode, `admit_cold`, ...); the enqueues of prefill and decode
are the host spans `cold_prefill` and `decode_dispatch` (tracing.py).

Plan-exactness contract: under a `mode="serve"` plan every program is
traced inside a `shard_hints.hints(serve_exact=True)` context, which (a)
forces activation gathers before the plan's replicated reduction
projections (gather-form TP — Fig. 14's gather-then-linear_o) and (b)
routes the paged decode kernels through shard_map with the arena's
kv-head dim partitioned (kernels/ops.py).  Every cross-device op is then
either a gather or per-head-local math, so sharded token streams are
bit-identical to single-device serving (tests/test_sharded_serving.py).

Throughput mode (`plan.exact=False`): serve plans trace under
`hints(serve_psum=True)` instead — column-sharded reduction projections
with one all-reduce each (Megatron form), and serve_pipeline plans swap
the drained GPipe decode program for the request-skewed schedule
(`_pipeline_skew_decode_fn`).  Streams then satisfy the token-match band
rather than bitwise equality (docs/serving.md §exactness contract).

Host-side policy lives in serving/scheduler.py; page accounting in
serving/kv_manager.py; serving/engine.py composes the three.
"""
from __future__ import annotations

import contextlib
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core.packing import bucket_len
from repro.models import shard_hints
from repro.models.transformer import (
    Model, greedy_token_update, layer_plan,
)
from repro.serving.tracing import span

PAD_TOKEN = 0  # fed for finished/free slot rows; their logits are never read


class Executor:
    """Jit-program cache + plan placement for one serving engine."""

    def __init__(self, model: Model, params, plan=None,
                 quant_weights: bool = False, max_batch: int = 8,
                 cache_len: int = 0, buckets=()):
        self.model = model
        self.plan = plan
        self.quant_weights = bool(quant_weights)
        self.max_batch = max_batch
        self.cache_len = cache_len  # engine re-rounds it for paged mode
        self.buckets = tuple(sorted(buckets))
        if self.quant_weights:
            # int8 weight path (models/quantized.py): projections/MLP run
            # W8A8; with kv_dtype="int8" on top the decode loop is
            # integer-dominant — the paper's I-BERT datapath at scale
            from repro.models.quantized import quantize_params_for_serving
            params = quantize_params_for_serving(params)
        self._param_shardings = None
        self._cache_shardings = None
        self._rep = None
        self._hints_kw = None
        if plan is not None:
            # param specs derive from the leaf tree actually served: under
            # quant_weights that is the quantized tree — the rule table
            # shards each "q" like its parent weight and replicates "s" —
            # which is what lets W8A8 compose with a ClusterPlan
            plan.param_specs = plan.specs_for_params(
                jax.eval_shape(lambda: params))
            self._param_shardings = jax.tree.map(plan.sharding,
                                                 plan.param_specs)
            self._rep = plan.sharding(P())
            params = jax.device_put(params, self._param_shardings)
            if plan.mode == "serve":
                ex = getattr(plan, "exact", True)
                self._hints_kw = dict(mesh=plan.mesh, dp_axes=plan.axes.dp,
                                      tp_axis=plan.axes.tp, serve_exact=ex,
                                      serve_psum=not ex)
        self.params = params
        self._jit_prefill: Dict = {}
        self._jit_decode: Dict = {}
        self._jit_insert = None
        self._jit_admit_lane = None
        self._jit_admit_cold: Dict = {}
        self._jit_admit_hit = None
        self._jit_admit_lane_paged = None
        self._jit_park = None
        # speculative decoding (set_draft): the draft model's params and
        # program caches — spec programs are keyed per ladder depth like
        # the horizon programs
        self.draft_model = None
        self.draft_params = None
        self._jit_spec: Dict = {}
        self._jit_draft_prefill: Dict = {}
        self._jit_admit_cold_draft: Dict = {}
        self._jit_catchup: Dict = {}
        # disaggregated prefill/decode pools (set_disagg): a second param
        # copy + mesh for the prefill pool, and the page-shipping programs
        self.prefill_params = None
        self.prefill_mesh = None
        self.decode_mesh = None
        self._prefill_sharding = None
        self._decode_sharding = None
        self._jit_prefill_admit: Dict = {}
        self._jit_ship: Dict = {}

    def set_disagg(self, prefill_devs, decode_devs) -> None:
        """Split the executor across disjoint device pools: prefill
        programs (and the staging arena they scatter into) live on
        ``prefill_devs``, the decode arena / lane state / decode-side
        admission programs on ``decode_devs``.  Params are committed to
        both pools — prefill reads ``prefill_params``, everything else the
        decode copy — so every program's placement follows its committed
        operands and the only cross-pool traffic is `ship_pages`'
        explicit block transfer.  Composes with plan=None engines only
        (a ClusterPlan already owns placement)."""
        assert self.plan is None, \
            "disagg needs plan=None (a plan already owns placement)"
        from jax.sharding import NamedSharding

        from repro.serving.replica import make_group_mesh
        self.prefill_mesh = make_group_mesh(
            prefill_devs, (len(prefill_devs),), ("pool",))
        self.decode_mesh = make_group_mesh(
            decode_devs, (len(decode_devs),), ("pool",))
        self._prefill_sharding = NamedSharding(self.prefill_mesh, P())
        self._decode_sharding = NamedSharding(self.decode_mesh, P())
        self.prefill_params = jax.device_put(self.params,
                                             self._prefill_sharding)
        self.params = jax.device_put(self.params, self._decode_sharding)

    def set_draft(self, draft_model: Model, draft_params) -> None:
        """Install the speculative-decoding draft model.  Draft weights
        ride the same quantization switch as the target; under a plan they
        are *replicated* (a reduced-class draft is far below the sharding
        payoff point, and replication keeps the draft scan free of
        collectives so only the verify pass pays TP gathers)."""
        if self.quant_weights:
            from repro.models.quantized import quantize_params_for_serving
            draft_params = quantize_params_for_serving(draft_params)
        if self.plan is not None:
            draft_params = jax.device_put(draft_params, self._rep)
        self.draft_model = draft_model
        self.draft_params = draft_params

    def init_draft_caches(self, page_size: int, num_pages: int,
                          max_pages: int, kv_dtype: str = "bf16"):
        """The draft model's paged arena — replicated under a plan, like
        its params (same rationale)."""
        caches = self.draft_model.init_paged_cache(
            self.max_batch, num_pages, page_size, max_pages,
            kv_dtype=kv_dtype)
        if self.plan is not None:
            caches = jax.device_put(caches, self._rep)
        return caches

    # -- trace context --------------------------------------------------------

    @contextlib.contextmanager
    def _ctx(self):
        """serve_exact hints are read at trace time, so every jitted call
        goes through here; re-entering an already-traced program costs one
        threadlocal write."""
        if self._hints_kw is None:
            yield
        else:
            with shard_hints.hints(**self._hints_kw):
                yield

    def _call(self, fn, *args):
        with self._ctx():
            return fn(*args)

    # -- cache construction / placement ---------------------------------------

    def init_caches(self, paged: bool, page_size: int = 0,
                    num_pages: int = 0, max_pages: int = 0,
                    kv_dtype: str = "bf16"):
        """Build the persistent serving cache and place it under the plan
        (paged arenas: kv-head-sharded; dense slot tables: serve-mode slot
        specs; serve_pipeline: stage-sharded scan leaves)."""
        if paged:
            caches = self.model.init_paged_cache(
                self.max_batch, num_pages, page_size, max_pages,
                kv_dtype=kv_dtype)
        else:
            caches = self.model.init_cache(self.max_batch, self.cache_len)
        if self.plan is not None:
            specs = self.plan.specs_for_caches(
                jax.eval_shape(lambda: caches), batch=self.max_batch,
                slot_table=True, paged=paged)
            self._cache_shardings = jax.tree.map(self.plan.sharding, specs)
            caches = jax.device_put(caches, self._cache_shardings)
        elif self._decode_sharding is not None:
            caches = jax.device_put(caches, self._decode_sharding)
        return caches

    def fresh_state(self, caches, paged: bool,
                    draft_caches=None) -> Dict[str, Any]:
        """Device decode state: mutated only through the programs below,
        fetched only as (n, B) token blocks at horizon boundaries."""
        b = self.max_batch
        st = {"caches": caches,
              "cur": jnp.full((b,), PAD_TOKEN, jnp.int32),
              "active": jnp.zeros((b,), bool),
              "eos": jnp.full((b,), -1, jnp.int32),
              "budget": jnp.zeros((b,), jnp.int32)}
        if paged:
            st.update(forced=jnp.zeros((b, self.cache_len), jnp.int32),
                      flen=jnp.zeros((b,), jnp.int32),
                      fptr=jnp.zeros((b,), jnp.int32))
        if draft_caches is not None:
            st["draft_caches"] = draft_caches
        if self._decode_sharding is not None:
            st = jax.device_put(st, self._decode_sharding)
        return st

    # -- prefill ---------------------------------------------------------------

    def _prefill_fn(self, bucket: int, batch: int, cache_slots: int):
        key = (bucket, batch, cache_slots)
        if key not in self._jit_prefill:
            model = self.model

            def prefill(params, tokens, positions, lengths):
                caches = model.init_cache(batch, cache_slots)
                logits, caches = model.prefill(
                    params, caches, tokens=tokens, positions=positions,
                    last_idx=lengths - 1)
                return logits, caches

            kw = {}
            if self.plan is not None:
                kw["in_shardings"] = (self._param_shardings, self._rep,
                                      self._rep, self._rep)
            self._jit_prefill[key] = jax.jit(prefill, **kw)
        return self._jit_prefill[key]

    def prefill_prompts(self, prompts, batch: int,
                        bucket_cache: bool = False):
        """Bucketed left-aligned batched prefill; returns (logits, caches).

        bucket_cache=True writes a bucket-sized cache (the slot engine's
        admission path pads it up to the slot row on insert); otherwise
        the cache spans cache_len and is decoded into directly (waves).
        """
        with span("cold_prefill"):
            maxlen = max(len(p) for p in prompts)
            bucket = bucket_len(maxlen, self.buckets, lane=8)
            cache_slots = bucket if bucket_cache else self.cache_len
            toks = np.zeros((batch, bucket), np.int32)
            # pad positions = 2^30 so the causal mask can never attend to
            # them (and cache slot i == position i for decode)
            pos = np.full((batch, bucket), 2 ** 30, np.int32)
            lengths = np.ones((batch,), np.int32)
            for i, p in enumerate(prompts):
                n = len(p)
                toks[i, :n] = p
                pos[i, :n] = np.arange(n)
                lengths[i] = n
            # under disagg every prefill belongs to the prefill pool: the
            # pool-committed param copy pins the dispatch there
            params = (self.prefill_params if self.prefill_params is not None
                      else self.params)
            return self._call(self._prefill_fn(bucket, batch, cache_slots),
                              params, jnp.asarray(toks), jnp.asarray(pos),
                              jnp.asarray(lengths))

    @property
    def warm_buckets(self):
        return [b for (b, n, _) in self._jit_prefill if n == 1]

    # -- fused decode ----------------------------------------------------------

    def decode_fn(self, n: int, paged: bool):
        """Fused n-step decode program (compiled once per horizon length;
        jax.jit re-specializes per batch shape for the wave engine's
        variable waves).  The paged variant threads the forced-token queue
        (prefix-hit suffix ingest) through the same fused loop; a
        serve_pipeline plan swaps in the stage-streaming program."""
        key = (n, paged)
        if key in self._jit_decode:
            return self._jit_decode[key]
        model = self.model
        if self.plan is not None and self.plan.mode == "serve_pipeline":
            if getattr(self.plan, "exact", True):
                assert not paged, \
                    "exact serve_pipeline streams the dense slot path"
                self._jit_decode[key] = self._pipeline_decode_fn(n)
            else:
                assert paged, \
                    "throughput serve_pipeline runs the paged arena path"
                self._jit_decode[key] = self._pipeline_skew_decode_fn(n)
        elif paged:
            # `pfn` (jit_pfn): benchmarks/chip finds the paged decode program
            # in a device trace by this name, and no other program's name
            # holds it
            def pfn(params, caches, token, active, eos, budget,
                    forced, flen, fptr):
                return model.decode_steps(
                    params, caches, token, active, n, eos_id=eos,
                    budget=budget, pad_token=PAD_TOKEN, forced=forced,
                    forced_len=flen, forced_ptr=fptr)

            kw = {}
            if self.plan is not None:
                kw["in_shardings"] = ((self._param_shardings,
                                       self._cache_shardings)
                                      + (self._rep,) * 7)
                kw["out_shardings"] = ((self._rep,) * 5
                                       + (self._cache_shardings,))
            self._jit_decode[key] = jax.jit(pfn, donate_argnums=(1,), **kw)
        else:

            def decode_dense(params, caches, token, active, eos, budget):
                return model.decode_steps(params, caches, token, active, n,
                                          eos_id=eos, budget=budget,
                                          pad_token=PAD_TOKEN)

            kw = {}
            if self.plan is not None:
                kw["in_shardings"] = ((self._param_shardings,
                                       self._cache_shardings)
                                      + (self._rep,) * 4)
                kw["out_shardings"] = ((self._rep,) * 4
                                       + (self._cache_shardings,))
            self._jit_decode[key] = jax.jit(decode_dense, donate_argnums=(1,),
                                            **kw)
        return self._jit_decode[key]

    def decode(self, st: Dict[str, Any], n: int, paged: bool):
        """Run one fused dispatch against the state dict; returns the
        (n, B) token block, st updated in place."""
        with span("decode_dispatch"):
            fn = self.decode_fn(n, paged)
            if paged:
                toks, cur, active, budget, fptr, caches = self._call(
                    fn, self.params, st["caches"], st["cur"], st["active"],
                    st["eos"], st["budget"], st["forced"], st["flen"],
                    st["fptr"])
                st.update(caches=caches, cur=cur, active=active,
                          budget=budget, fptr=fptr)
            else:
                toks, cur, active, budget, caches = self._call(
                    fn, self.params, st["caches"], st["cur"], st["active"],
                    st["eos"], st["budget"])
                st.update(caches=caches, cur=cur, active=active,
                          budget=budget)
            return toks

    def warm_ladder(self, st: Dict[str, Any], horizons) -> None:
        """Compile the whole horizon ladder + paged lane-state programs by
        executing them on the empty (all-inactive) state — semantically a
        no-op, but a compile that instead fired mid-serving would stall
        every resident lane.  The radix tree makes the horizon schedule
        state-dependent, so "the warmup pass saw it" does not cover later
        passes the way it does for dense slots."""
        for n in horizons:
            self.decode(st, n, paged=True)
        trash = np.zeros((st["caches"]["pt"].shape[1],), np.int32)
        self.admit_hit(st, 0, trash, 0, trash)
        self.admit_lane_paged(st, 0, PAD_TOKEN, -1, 0,
                              np.zeros((0,), np.int32), 0)
        self.park_lane(st, 0)

    # -- speculative decoding --------------------------------------------------

    def spec_fn(self, k: int):
        """Fused speculative program for draft depth `k`: the k+1-step
        draft scan, the single batched target verify (Sq = k+1 through the
        paged multi-query branch), and the acceptance/emission state
        machine — one dispatch, one host fetch, up to k+1 tokens per lane.
        Replaces the horizon decode program for spec-mode dispatches and
        handles the forced-token queue itself, so admissions need no extra
        programs."""
        if k in self._jit_spec:
            return self._jit_spec[k]
        model, draft = self.model, self.draft_model

        def spec_decode(params, dparams, caches, dcaches, token, active, eos,
                        budget, forced, flen, fptr):
            toks, cur, act, rem, fptr, caches, dcaches, _ = \
                model.spec_decode_step(
                    params, caches, token, active, k, draft, dparams,
                    dcaches, eos_id=eos, budget=budget, pad_token=PAD_TOKEN,
                    forced=forced, forced_len=flen, forced_ptr=fptr)
            return toks, cur, act, rem, fptr, caches, dcaches

        kw = {}
        if self.plan is not None:
            kw["in_shardings"] = ((self._param_shardings, self._rep,
                                   self._cache_shardings, self._rep)
                                  + (self._rep,) * 7)
            kw["out_shardings"] = ((self._rep,) * 5
                                   + (self._cache_shardings, self._rep))
        self._jit_spec[k] = jax.jit(spec_decode, donate_argnums=(2, 3), **kw)
        return self._jit_spec[k]

    def spec_decode(self, st: Dict[str, Any], k: int):
        """One speculative dispatch; returns the (k+1, B) token block,
        st (both cache trees included) updated in place."""
        with span("decode_dispatch"):
            toks, cur, active, budget, fptr, caches, dcaches = self._call(
                self.spec_fn(k), self.params, self.draft_params,
                st["caches"], st["draft_caches"], st["cur"], st["active"],
                st["eos"], st["budget"], st["forced"], st["flen"],
                st["fptr"])
        st.update(caches=caches, draft_caches=dcaches, cur=cur,
                  active=active, budget=budget, fptr=fptr)
        return toks

    def warm_spec(self, st: Dict[str, Any], ladder) -> None:
        """Compile the spec ladder on the empty state (same rationale as
        warm_ladder)."""
        for k in ladder:
            self.spec_decode(st, k)

    def draft_prefill_prompts(self, prompts, batch: int):
        """Bucketed batch-1 prefill on the *draft* model (cold draft-lane
        admission; a prefix-hit lane prefills prompt[:hit_len] — the draft
        has no radix tree, but hit lengths are page-aligned so the suffix
        ingests in lockstep through the spec program's forced queue)."""
        from repro.core.packing import bucket_len as _bl
        maxlen = max(len(p) for p in prompts)
        bucket = _bl(maxlen, self.buckets, lane=8)
        key = (bucket, batch)
        if key not in self._jit_draft_prefill:
            draft = self.draft_model

            def draft_prefill(dparams, tokens, positions, lengths):
                caches = draft.init_cache(batch, bucket)
                return draft.prefill(dparams, caches, tokens=tokens,
                                     positions=positions,
                                     last_idx=lengths - 1)

            kw = {}
            if self.plan is not None:
                kw["in_shardings"] = (self._rep,) * 4
            self._jit_draft_prefill[key] = jax.jit(draft_prefill, **kw)
        toks = np.zeros((batch, bucket), np.int32)
        pos = np.full((batch, bucket), 2 ** 30, np.int32)
        lengths = np.ones((batch,), np.int32)
        for i, p in enumerate(prompts):
            n = len(p)
            toks[i, :n] = p
            pos[i, :n] = np.arange(n)
            lengths[i] = n
        _, caches = self._call(self._jit_draft_prefill[key],
                               self.draft_params, jnp.asarray(toks),
                               jnp.asarray(pos), jnp.asarray(lengths))
        return caches, bucket

    def admit_cold_draft(self, st, slot: int, small, pt_row, pos0: int,
                         reset, write_pages: np.ndarray,
                         bucket: int) -> None:
        """Scatter a draft bucket prefill into the lane's draft-arena
        pages (the draft twin of admit_cold)."""
        key = (bucket, len(write_pages))
        if key not in self._jit_admit_cold_draft:
            draft = self.draft_model

            def admit_cold_draft(big, small, slot, pt_row, pos0, reset, wp):
                return draft.admit_lane_cache(big, slot, pt_row, pos0,
                                              reset, small=small,
                                              write_pages=wp)

            kw = {}
            if self.plan is not None:
                kw["out_shardings"] = self._rep
            self._jit_admit_cold_draft[key] = jax.jit(
                admit_cold_draft, donate_argnums=(0,), **kw)
        st["draft_caches"] = self._call(
            self._jit_admit_cold_draft[key], st["draft_caches"], small,
            slot, jnp.asarray(pt_row), pos0, jnp.asarray(reset),
            jnp.asarray(write_pages))

    def draft_catchup(self, st, tokens: np.ndarray,
                      lag: np.ndarray) -> None:
        """Re-synchronize draft lanes after spec-disabled dispatches: feed
        each lane the stream tokens the target consumed while the draft
        sat idle (tokens[b, :lag[b]]; columns past a lane's lag are
        masked, so its cache rows and position counter stop advancing at
        exactly the target's position).  Compiled per power-of-two width
        like the horizon ladder."""
        n = max(1, int(tokens.shape[1]))
        n_pad = 1 << (n - 1).bit_length()
        if n_pad not in self._jit_catchup:
            draft = self.draft_model

            def draft_catchup(dparams, dcaches, toks, lag):
                def step(caches, xs):
                    tok, j = xs
                    live = j < lag
                    _, caches = draft.decode_step(dparams, caches, tok,
                                                  active=live)
                    return caches, None

                dcaches, _ = jax.lax.scan(
                    step, dcaches,
                    (toks.T, jnp.arange(n_pad, dtype=jnp.int32)))
                return dcaches

            kw = {}
            if self.plan is not None:
                kw["in_shardings"] = (self._rep,) * 4
                kw["out_shardings"] = self._rep
            self._jit_catchup[n_pad] = jax.jit(draft_catchup,
                                               donate_argnums=(1,), **kw)
        padded = np.zeros((tokens.shape[0], n_pad), np.int32)
        padded[:, :tokens.shape[1]] = tokens
        st["draft_caches"] = self._call(
            self._jit_catchup[n_pad], self.draft_params,
            st["draft_caches"], jnp.asarray(padded),
            jnp.asarray(lag, np.int32))

    # -- slot / lane updates ---------------------------------------------------

    def insert(self, big, small, slot: int):
        """Write a batch-1 prefill cache into a dense slot row."""
        if self._jit_insert is None:
            model = self.model

            def insert_slot(big, small, slot):
                return model.insert_prefill_cache(big, small, slot)

            kw = {}
            if self.plan is not None:
                kw["out_shardings"] = self._cache_shardings
            self._jit_insert = jax.jit(insert_slot, donate_argnums=(0,), **kw)
        return self._call(self._jit_insert, big, small, slot)

    def admit_hit(self, st, slot: int, pt_row, pos0: int, reset) -> None:
        """Point a lane at its (shared prefix + own) pages; the suffix
        arrives later through the decode loop's forced queue."""
        if self._jit_admit_hit is None:
            model = self.model

            def admit_hit(big, slot, pt_row, pos0, reset):
                return model.admit_lane_cache(big, slot, pt_row, pos0, reset)

            kw = {}
            if self.plan is not None:
                kw["out_shardings"] = self._cache_shardings
            self._jit_admit_hit = jax.jit(admit_hit, donate_argnums=(0,), **kw)
        st["caches"] = self._call(self._jit_admit_hit, st["caches"], slot,
                                  jnp.asarray(pt_row), pos0,
                                  jnp.asarray(reset))

    def admit_cold(self, st, slot: int, small, pt_row, pos0: int, reset,
                   write_pages: np.ndarray, bucket: int) -> None:
        """Scatter a bucket prefill cache into the lane's arena pages."""
        key = (bucket, len(write_pages))
        if key not in self._jit_admit_cold:
            model = self.model

            def admit_cold(big, small, slot, pt_row, pos0, reset, wp):
                return model.admit_lane_cache(big, slot, pt_row, pos0,
                                              reset, small=small,
                                              write_pages=wp)

            kw = {}
            if self.plan is not None:
                kw["out_shardings"] = self._cache_shardings
            self._jit_admit_cold[key] = jax.jit(admit_cold,
                                                donate_argnums=(0,), **kw)
        st["caches"] = self._call(
            self._jit_admit_cold[key], st["caches"], small, slot,
            jnp.asarray(pt_row), pos0, jnp.asarray(reset),
            jnp.asarray(write_pages))

    # -- disaggregated page shipping (set_disagg) ------------------------------

    def init_prefill_arena(self, page_size: int, num_pages: int,
                           max_pages: int, kv_dtype: str = "bf16"):
        """The prefill pool's staging arena: a batch-1 paged cache on the
        prefill mesh.  One admission stages at a time (lane 0), so
        ``num_pages = max_pages + 1`` (+ trash page) always covers it."""
        arena = self.model.init_paged_cache(
            1, num_pages, page_size, max_pages, kv_dtype=kv_dtype)
        return jax.device_put(arena, self._prefill_sharding)

    def prefill_admit(self, arena, small, pt_row, pos0: int, reset,
                      write_pages: np.ndarray, bucket: int):
        """Scatter a bucket prefill cache into the staging arena's pages
        (admit_cold's scatter, aimed at the prefill pool's arena at lane
        0).  The page *contents* this writes are exactly what admit_cold
        would have written into the decode arena — int8 arenas quantize on
        the way in here, before shipping — which is what makes disagg
        streams bit-identical to colocated serving."""
        key = (bucket, len(write_pages))
        if key not in self._jit_prefill_admit:
            model = self.model

            def prefill_admit(big, small, pt_row, pos0, reset, wp):
                return model.admit_lane_cache(big, 0, pt_row, pos0, reset,
                                              small=small, write_pages=wp)

            self._jit_prefill_admit[key] = jax.jit(prefill_admit,
                                                   donate_argnums=(0,))
        return self._call(self._jit_prefill_admit[key], arena, small,
                          jnp.asarray(pt_row), pos0, jnp.asarray(reset),
                          jnp.asarray(write_pages))

    def ship_pages(self, arena, st, src_pages, dst_pages) -> None:
        """Ship completed KV pages from the prefill arena into the decode
        arena: ONE batched gather on the prefill mesh, one cross-pool
        block transfer, one batched scatter (donated) into the decode
        caches.  Every arena leaf rides the same tree map, so an int8
        arena's `k_scale`/`v_scale` planes travel with their pages.

        Programs are keyed per power-of-two page count; both index
        vectors pad with the trash page 0, which is safe on each side —
        trash-page kpos is sentinel by construction (inactive-lane writes
        are sentinel-stamped, attention.py), so a trash→trash copy cannot
        make stale keys reachable."""
        n = len(src_pages)
        assert n == len(dst_pages) and n > 0
        n_pad = 1 << (n - 1).bit_length()
        src = np.zeros((n_pad,), np.int32)
        src[:n] = src_pages
        dst = np.zeros((n_pad,), np.int32)
        dst[:n] = dst_pages
        if n_pad not in self._jit_ship:
            from repro.models.transformer import paged_cache_map

            def ship_gather(scan, tail, idx):
                return paged_cache_map(
                    lambda ax, name, b: jnp.take(b, idx, axis=ax),
                    {"scan": scan, "tail": tail})

            def ship_scatter(caches, blk, idx):
                sub = paged_cache_map(
                    lambda ax, name, b, s: (b.at[idx].set(s) if ax == 0
                                            else b.at[:, idx].set(s)),
                    {"scan": caches["scan"], "tail": caches["tail"]}, blk)
                return dict(caches, scan=sub["scan"], tail=sub["tail"])

            self._jit_ship[n_pad] = (
                jax.jit(ship_gather),
                jax.jit(ship_scatter, donate_argnums=(0,)))
        gather, scatter = self._jit_ship[n_pad]
        blk = gather(arena["scan"], arena["tail"], jnp.asarray(src))
        blk = jax.device_put(blk, self._decode_sharding)  # the pool hop
        st["caches"] = scatter(st["caches"], blk, jnp.asarray(dst))

    def admit_lane(self, st, sl: int, tok: int, eos_id: int,
                   bud: int) -> None:
        """One fused update of the device decode state for an admission
        (four eager .at[].set dispatches cost ~4x this on small hosts)."""
        if self._jit_admit_lane is None:

            def admit_lane(cur, active, eos, budget, sl, tok, eos_id, bud):
                return (cur.at[sl].set(tok), active.at[sl].set(True),
                        eos.at[sl].set(eos_id), budget.at[sl].set(bud))

            self._jit_admit_lane = jax.jit(admit_lane,
                                           donate_argnums=(0, 1, 2, 3))
        st["cur"], st["active"], st["eos"], st["budget"] = self._call(
            self._jit_admit_lane, st["cur"], st["active"], st["eos"],
            st["budget"], sl, tok, eos_id, bud)

    def admit_lane_paged(self, st, sl: int, tok: int, eos_id: int, bud: int,
                         forced_rest, flen: int) -> None:
        """Fused lane-state update for a paged admission: decode state plus
        the forced-token (suffix-ingest) queue row."""
        if self._jit_admit_lane_paged is None:

            def admit_lane_paged(cur, active, eos, budget, forced, fl_,
                                 fptr, sl, tok, eos_id, bud, frow, fl):
                return (cur.at[sl].set(tok), active.at[sl].set(True),
                        eos.at[sl].set(eos_id), budget.at[sl].set(bud),
                        forced.at[sl].set(frow), fl_.at[sl].set(fl),
                        fptr.at[sl].set(0))

            self._jit_admit_lane_paged = jax.jit(
                admit_lane_paged, donate_argnums=(0, 1, 2, 3, 4, 5, 6))
        frow = np.zeros((self.cache_len,), np.int32)
        if len(forced_rest):
            frow[:len(forced_rest)] = forced_rest
        (st["cur"], st["active"], st["eos"], st["budget"], st["forced"],
         st["flen"], st["fptr"]) = self._call(
            self._jit_admit_lane_paged, st["cur"], st["active"], st["eos"],
            st["budget"], st["forced"], st["flen"], st["fptr"], sl, tok,
            eos_id, bud, jnp.asarray(frow), flen)

    def park_lane(self, st, sl: int) -> None:
        """Deactivate a lane on device (preemption): masked writes go to
        the trash page from the next step on."""
        if self._jit_park is None:

            def park_lane(cur, active, sl):
                return cur.at[sl].set(PAD_TOKEN), active.at[sl].set(False)

            self._jit_park = jax.jit(park_lane, donate_argnums=(0, 1))
        st["cur"], st["active"] = self._call(self._jit_park, st["cur"],
                                             st["active"], sl)

    # -- stage-pipelined decode (mode="serve_pipeline") ------------------------

    def _pipeline_decode_fn(self, n: int):
        """Fused n-step decode streamed through the `stage` mesh axis.

        Stage s holds its contiguous slice of the scan-stacked layer
        periods (plan specs) and each decode step runs the GPipe schedule
        from core/pipeline over *lane microbatches*: at tick t, stage s
        applies its layers to microbatch (t - s) and ppermutes the hidden
        state forward — the paper's gateway-to-gateway encoder stream with
        decode micro-steps as the traffic.  The final hidden state is
        psum-shared so argmax/EOS/budget bookkeeping (the exact
        `greedy_token_update` used by `Model.decode_steps`) runs
        replicated, making the pipelined stream bit-identical to the
        single-device fused loop.
        """
        from repro.core.pipeline import gpipe_forward_perm, pipeline_steps
        from repro.models.layers import lm_head, norm
        from repro.models.transformer import block_apply

        model, plan, cfg = self.model, self.plan, self.model.cfg
        mesh, axis = plan.mesh, plan.axes.stage
        n_stages = mesh.shape[axis]
        n_rep, tail, kinds = layer_plan(cfg)
        if tail or n_rep % n_stages:
            raise ValueError(
                f"serve_pipeline needs the scan-stacked periods to divide "
                f"the stage axis: n_rep={n_rep}, tail={tail}, "
                f"stages={n_stages}")
        b = self.max_batch
        n_micro = n_stages if b % n_stages == 0 else 1
        mb = b // n_micro
        steps = pipeline_steps(n_micro, n_stages)
        fwd = gpipe_forward_perm(n_stages)
        np_ = len(kinds)

        def body(scan_p, rest_p, scan_c, pos0, token, active, eos, budget):
            sidx = jax.lax.axis_index(axis)

            def decode_one(carry, _):
                cur, act, rem, pos, sc = carry
                x = model.embed_inputs(rest_p, tokens=cur[:, None])
                positions = pos[:, None]
                xm = x.reshape(n_micro, mb, 1, x.shape[-1])
                buf = jnp.zeros_like(xm[0])
                out = jnp.zeros_like(xm)

                def tick(t, c2):
                    buf, out, sc = c2
                    m = t - sidx  # microbatch this stage works on
                    stage_on = (m >= 0) & (m < n_micro)
                    row0 = jnp.clip(m, 0, n_micro - 1) * mb
                    x_in = jnp.where(sidx == 0,
                                     xm[jnp.minimum(t, n_micro - 1)], buf)
                    pos_sl = jax.lax.dynamic_slice_in_dim(
                        positions, row0, mb, 0)
                    act_sl = jax.lax.dynamic_slice_in_dim(act, row0, mb, 0)

                    def period_body(h, xs):
                        pp, pc = xs
                        pc_sl = jax.tree.map(
                            lambda a: jax.lax.dynamic_slice_in_dim(
                                a, row0, mb, 0), pc)
                        new_sl = {}
                        for i in range(np_):
                            h, ns, _, _ = block_apply(cfg, i, pp[f"b{i}"],
                                                      h, pos_sl, None,
                                                      pc_sl[f"b{i}"])
                            new_sl[f"b{i}"] = ns

                        def upd(full, nsl):
                            # commit the microbatch rows only for active
                            # lanes on an active stage — the pipelined
                            # form of decode_step's cache_map where-mask
                            old = jax.lax.dynamic_slice_in_dim(
                                full, row0, mb, 0)
                            keep = stage_on & act_sl.reshape(
                                (mb,) + (1,) * (nsl.ndim - 1))
                            return jax.lax.dynamic_update_slice_in_dim(
                                full, jnp.where(keep, nsl.astype(full.dtype),
                                                old), row0, 0)

                        return h, jax.tree.map(upd, pc, new_sl)

                    h, sc = jax.lax.scan(period_body, x_in, (scan_p, sc))
                    y = jnp.where(stage_on, h, buf)
                    oslot = t - (n_stages - 1)
                    write = (sidx == n_stages - 1) & (oslot >= 0)
                    out = jax.lax.cond(
                        write,
                        lambda o: jax.lax.dynamic_update_index_in_dim(
                            o, y, jnp.maximum(oslot, 0), 0),
                        lambda o: o, out)
                    buf = jax.lax.ppermute(y, axis, fwd)
                    return (buf, out, sc)

                _, out, sc = jax.lax.fori_loop(0, steps, tick,
                                               (buf, out, sc))
                # results live on the last stage; share them so the token
                # feedback loop runs replicated (0 + x is exact in bf16)
                out = jax.lax.psum(
                    jnp.where(sidx == n_stages - 1, out,
                              jnp.zeros_like(out)), axis)
                h = norm(out.reshape(b, 1, -1), rest_p["final_norm"], cfg)
                logits = lm_head(h, rest_p["embed"])[:, 0]
                emit, cur, still, rem = greedy_token_update(
                    logits, cur, act, rem, eos, PAD_TOKEN)
                pos = jnp.where(act, pos + 1, pos)
                return (cur, still, rem, pos, sc), emit

            (cur, act, rem, pos, sc), toks = jax.lax.scan(
                decode_one,
                (token.astype(jnp.int32), active, budget, pos0, scan_c),
                None, length=n)
            return toks, cur, act, rem, pos, sc

        def pipeline_decode(params, caches, token, active, eos, budget):
            rest_p = {k: v for k, v in params.items() if k != "scan"}
            toks, cur, act, rem, pos, sc = jax.shard_map(
                body, mesh=mesh,
                in_specs=(P(axis), P(), P(axis), P(), P(), P(), P(), P()),
                out_specs=(P(), P(), P(), P(), P(), P(axis)),
                check_vma=False,
            )(params["scan"], rest_p, caches["scan"], caches["pos"],
              token, active, eos, budget)
            return toks, cur, act, rem, {"scan": sc, "tail": {}, "pos": pos}

        kw = {}
        if self._param_shardings is not None:
            kw["in_shardings"] = ((self._param_shardings,
                                   self._cache_shardings)
                                  + (self._rep,) * 4)
            kw["out_shardings"] = ((self._rep,) * 4
                                   + (self._cache_shardings,))
        return jax.jit(pipeline_decode, donate_argnums=(1,), **kw)

    # -- request-skewed pipelined decode (mode="serve_pipeline", exact=False) --

    def _pipeline_skew_decode_fn(self, n: int):
        """Fused n-step *paged* decode on the request-skewed pipeline.

        The exact pipeline drains every decode step — each token costs
        ``n_micro + n_stages - 1`` ticks and the drain idles
        (stages-1)/stages of the mesh.  The throughput schedule
        (plan.exact=False) skews stages across *request lane groups*
        instead: the batch splits into n_stages contiguous groups, and at
        tick t stage s runs group (t-s) mod G at decode step (t-s) // G —
        while stage s holds group g's step k, stage s-1 is already on
        group g+1, so the only bubbles left are the S-1 fill/drain ticks
        of the whole horizon: n*S + S - 1 ticks for n steps against
        n*(2S - 1) exact (docs/perf.md has the accounting).

        Every stage keeps its *own* position counters (it ingests group
        g's step-k token S-1 ticks after stage 0 did) and the last stage
        commits a group's step — the verbatim `decode_steps` forced-queue
        state machine on that group's rows — into the replicated lane
        state via a psum-delta every stage folds in.  Group g's step-k
        commit lands at tick g + k*G + S - 1 and the earliest step-(k+1)
        read of those rows is at tick g + (k+1)*G (G = S), so each stage
        observes exactly the state the sequential fused loop would have:
        the schedule changes *when* lanes decode, never *what* they
        decode, and streams differ from exact plans only through float
        reduction order elsewhere in the plan.

        The paged arenas ride the stage axis for free: `_stage_spec`
        shards every scan-stacked leaf's leading period dim, which for an
        arena leaf (n_rep, P, ps, KVH, hd) leaves stage s holding only its
        own layers' pages — pipeline depth multiplies usable KV HBM
        (kv_manager.kv_page_bytes(shards=)); the page table and counters
        stay replicated routing metadata.
        """
        from repro.core.pipeline import gpipe_forward_perm
        from repro.models.layers import lm_head, norm
        from repro.models.transformer import block_apply

        model, plan, cfg = self.model, self.plan, self.model.cfg
        mesh, axis = plan.mesh, plan.axes.stage
        n_stages = mesh.shape[axis]
        n_rep, tail, kinds = layer_plan(cfg)
        if tail or n_rep % n_stages:
            raise ValueError(
                f"serve_pipeline needs the scan-stacked periods to divide "
                f"the stage axis: n_rep={n_rep}, tail={tail}, "
                f"stages={n_stages}")
        b = self.max_batch
        if b % n_stages:
            raise ValueError(
                f"request-skewed serve_pipeline needs max_batch divisible "
                f"by the stage count: batch={b}, stages={n_stages}")
        n_groups = n_stages
        mb = b // n_groups
        total = n * n_groups + n_stages - 1
        fwd = gpipe_forward_perm(n_stages)
        np_ = len(kinds)
        fcap = self.cache_len

        def body(scan_p, rest_p, scan_c, pt, pos0, token, active, eos,
                 budget, forced, flen, fptr):
            sidx = jax.lax.axis_index(axis)
            buf0 = jnp.zeros_like(
                model.embed_inputs(rest_p, tokens=token[:mb][:, None]))

            def tick(t, c2):
                # lane state rides as one (4, b) int32 array — rows are
                # (cur token, active, budget, forced ptr) — so the commit
                # below is a single psum, not four (collective dispatch on
                # the host mesh is the skew schedule's marginal cost)
                buf, out, sc, pos_s, state = c2
                m = t - sidx  # global micro-step this stage works on
                stage_on = (m >= 0) & (m < n * n_groups)
                mc = jnp.clip(m, 0, n * n_groups - 1)
                g = mc % n_groups  # lane group
                k_step = mc // n_groups  # its decode step
                row0 = g * mb

                st_sl = jax.lax.dynamic_slice(state, (0, row0), (4, mb))
                cur_sl, rem_sl, fp_sl = st_sl[0], st_sl[2], st_sl[3]
                act_sl = st_sl[1].astype(bool)
                pos_sl = jax.lax.dynamic_slice_in_dim(pos_s, row0, mb, 0)
                pt_sl = jax.lax.dynamic_slice_in_dim(pt, row0, mb, 0)
                x0 = model.embed_inputs(rest_p, tokens=cur_sl[:, None])
                x_in = jnp.where(sidx == 0, x0, buf)
                # arena writes are active-gated inside attention (inactive
                # or off-schedule rows land on the trash page), so the
                # stage mask composes with the lane mask directly
                wr = act_sl & stage_on

                def period_body(h, xs):
                    pp, pc = xs
                    new_pc = {}
                    for i in range(np_):
                        h, ns, _, _ = block_apply(
                            cfg, i, pp[f"b{i}"], h, pos_sl[:, None], None,
                            pc[f"b{i}"], page_table=pt_sl, active=wr)
                        new_pc[f"b{i}"] = ns
                    return h, new_pc

                h, sc = jax.lax.scan(period_body, x_in, (scan_p, sc))
                y = jnp.where(stage_on, h, buf)

                # last stage: finish the group's step — logits + the
                # decode_steps forced-queue state machine on its rows
                do = stage_on & (sidx == n_stages - 1)
                hn = norm(y, rest_p["final_norm"], cfg)
                logits = lm_head(hn, rest_p["embed"])[:, 0]
                nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                fl_sl = jax.lax.dynamic_slice_in_dim(flen, row0, mb, 0)
                eos_sl = jax.lax.dynamic_slice_in_dim(eos, row0, mb, 0)
                fr_sl = jax.lax.dynamic_slice_in_dim(forced, row0, mb, 0)
                pending = fp_sl < fl_sl
                emitting = act_sl & ~pending
                emit = jnp.where(emitting, nxt, -1)
                rem_new = jnp.where(emitting, rem_sl - 1, rem_sl)
                still = act_sl & (pending
                                  | ((nxt != eos_sl) & (rem_new > 0)))
                feed = jnp.where(
                    pending,
                    fr_sl[jnp.arange(mb), jnp.minimum(fp_sl, fcap - 1)],
                    nxt)
                cur_new = jnp.where(still, feed, PAD_TOKEN).astype(jnp.int32)
                fp_new = jnp.where(act_sl & pending, fp_sl + 1, fp_sl)
                out = jnp.where(
                    do,
                    jax.lax.dynamic_update_slice(out, emit[None, :],
                                                 (k_step, row0)),
                    out)

                # exactly one stage has `do` per tick; a single psum-delta
                # over the packed state folds its row update into every
                # stage's replicated copy (int32 throughout, so exact)
                new_sl = jnp.stack([cur_new, still.astype(jnp.int32),
                                    rem_new, fp_new])
                upd = jax.lax.dynamic_update_slice(state, new_sl, (0, row0))
                upd = jnp.where(do, upd, state)
                state = state + jax.lax.psum(upd - state, axis)
                # this stage just ingested one token for its group's
                # active lanes: advance its own counters
                pos_s = jax.lax.dynamic_update_slice_in_dim(
                    pos_s, jnp.where(wr, pos_sl + 1, pos_sl), row0, 0)
                buf = jax.lax.ppermute(y, axis, fwd)
                return (buf, out, sc, pos_s, state)

            state0 = jnp.stack([token.astype(jnp.int32),
                                active.astype(jnp.int32),
                                budget.astype(jnp.int32),
                                fptr.astype(jnp.int32)])
            carry = (buf0, jnp.zeros((n, b), jnp.int32), scan_c, pos0,
                     state0)
            (_, out, sc, pos_s, state) = jax.lax.fori_loop(
                0, total, tick, carry)
            cur, act = state[0], state[1].astype(bool)
            rem, fp = state[2], state[3]
            # out lives on the last stage, counters agree on every stage
            # (same (group, step) sequence, same committed lane masks) —
            # share both so the outputs are replicated
            toks = jax.lax.psum(
                jnp.where(sidx == n_stages - 1, out, jnp.zeros_like(out)),
                axis)
            pos = jax.lax.psum(
                jnp.where(sidx == 0, pos_s, jnp.zeros_like(pos_s)), axis)
            return toks, cur, act, rem, fp, pos, sc

        def pipeline_skew_decode(params, caches, token, active, eos, budget,
                                 forced, flen, fptr):
            rest_p = {k: v for k, v in params.items() if k != "scan"}
            toks, cur, act, rem, fp, pos, sc = jax.shard_map(
                body, mesh=mesh,
                in_specs=(P(axis), P(), P(axis), P(), P(), P(), P(), P(),
                          P(), P(), P(), P()),
                out_specs=(P(), P(), P(), P(), P(), P(), P(axis)),
                check_vma=False,
            )(params["scan"], rest_p, caches["scan"], caches["pt"],
              caches["pos"], token, active, eos, budget, forced, flen,
              fptr)
            return toks, cur, act, rem, fp, {"scan": sc, "tail": {},
                                             "pos": pos,
                                             "pt": caches["pt"]}

        kw = {}
        if self._param_shardings is not None:
            kw["in_shardings"] = ((self._param_shardings,
                                   self._cache_shardings)
                                  + (self._rep,) * 7)
            kw["out_shardings"] = ((self._rep,) * 5
                                   + (self._cache_shardings,))
        return jax.jit(pipeline_skew_decode, donate_argnums=(1,), **kw)
