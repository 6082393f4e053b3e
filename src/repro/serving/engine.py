"""Serving engines: the composition root over the three serving layers.

serving/scheduler.py holds host-side policy only (admission ordering, the
decode-horizon ladder, preemption choice, stream reconciliation — no
jax); serving/executor.py holds every jitted program plus plan placement
(the `mode="serve"` kv-head-sharded paged path and the
`mode="serve_pipeline"` stage-streaming decode); serving/kv_manager.py
owns paged-KV memory (page pool, radix prefix cache, page tables).  This
module wires the three together behind the old monolith's public API
(semantics in docs/serving.md).
"""
from __future__ import annotations

import time
from typing import List, Optional, Tuple

import jax.numpy as jnp
import numpy as np

from repro.core.packing import bucket_len
from repro.models.transformer import Model
from repro.serving.executor import PAD_TOKEN, Executor
from repro.serving.kv_manager import (KVManager, kv_page_bytes,
                                      num_pages_for_hbm, paged_eligible)
from repro.serving.scheduler import Request, Scheduler
from repro.serving.tracing import PHASES, loop_clock, span

__all__ = ["ContinuousBatchingEngine", "WaveEngine", "ServingEngine",
           "Request", "EngineBase", "PAD_TOKEN", "kv_page_bytes",
           "num_pages_for_hbm"]


class EngineBase:
    """Shared composition: scheduler + executor, stats, prefill plumbing."""

    def __init__(self, model: Model, params, max_batch: int = 8,
                 buckets=(32, 64, 128, 256), greedy: bool = True,
                 deadline_s: float = 0.05, plan=None,
                 max_decode_len: int = 64, decode_horizon: int = 8,
                 quant_weights: bool = False):
        self.model = model
        self.max_batch = max_batch
        self.buckets = tuple(sorted(buckets))
        self.greedy = greedy
        self.plan = plan
        self.quant_weights = bool(quant_weights)
        self.paged = False  # ContinuousBatchingEngine may flip this
        # slot rows: prompt KV + decode headroom, fixed per engine
        self.cache_len = bucket_len(max(self.buckets), self.buckets,
                                    lane=8) + max_decode_len
        self.decode_horizon = decode_horizon
        self.sched = Scheduler(self.buckets, deadline_s, decode_horizon,
                               max_batch)
        self.executor = Executor(model, params, plan=plan,
                                 quant_weights=quant_weights,
                                 max_batch=max_batch,
                                 cache_len=self.cache_len,
                                 buckets=self.buckets)
        # counts, then the phase counters (tracing.py): host seconds of
        # each part of run(), added as the work happens
        self.stats = {"prefill_tokens": 0, "decode_steps": 0,
                      "decode_dispatches": 0, "device_syncs": 0,
                      **dict.fromkeys(PHASES, 0.0), "gc_collections": 0}

    params = property(lambda self: self.executor.params)
    policy = property(lambda self: self.sched.policy)

    def submit(self, req: Request) -> None:
        need = self.policy.bucket_of(len(req.prompt)) + req.max_new_tokens
        # speculation scatters up to spec_k rows past the committed
        # position; the lane's page table must cover the overshoot
        need += getattr(self, "spec_k", 0) if getattr(self, "spec", False) \
            else 0
        if need > self.cache_len:
            raise ValueError(
                f"request {req.rid}: bucket+budget {need} exceeds slot "
                f"cache_len {self.cache_len} (raise max_decode_len)")
        if self.paged and self.kv.pages_for(need) > self.kv.num_pages - 1:
            raise ValueError(
                f"request {req.rid}: needs {self.kv.pages_for(need)} pages,"
                f" pool has {self.kv.num_pages - 1} (raise num_pages)")
        self.sched.enqueue(req)

    def _prefill(self, prompts, batch: int, bucket_cache: bool = False):
        self.stats["prefill_tokens"] += int(sum(len(p) for p in prompts))
        return self.executor.prefill_prompts(prompts, batch,
                                             bucket_cache=bucket_cache)

    def _greedy_next(self, logits) -> np.ndarray:
        self.stats["device_syncs"] += 1
        with span("first_token_sync", self.stats, "sync_s"):
            return np.asarray(jnp.argmax(logits, -1), np.int32)

    def _fetch_block(self, toks, t_step: float) -> np.ndarray:
        """The block sync, the only per-dispatch device-to-host read; it
        closes the dispatch's decode_s, counted from `t_step`, taken
        just before the dispatch was enqueued."""
        with span("block_sync", self.stats, "sync_s"):
            block = np.asarray(toks)
        self.stats["decode_s"] += time.perf_counter() - t_step
        return block


class ContinuousBatchingEngine(EngineBase):
    """Slot-asynchronous scheduler: admit into freed slots between steps.

    ``paged`` (default "auto") swaps dense slot rows for the page arena +
    radix prefix cache; ``kv_dtype="int8"`` quantizes the arena;
    ``quant_weights=True`` serves W8A8 — all three compose with a
    ClusterPlan, whose serve mode shards the arena's kv-head dim across
    the mesh (docs/serving.md §sharded serving)."""

    def __init__(self, *args, paged="auto", page_size: int = 16,
                 num_pages: Optional[int] = None,
                 max_hit_suffix: Optional[int] = None,
                 kv_dtype: str = "bf16",
                 spec_config: Optional[dict] = None,
                 disagg: Optional[Tuple[int, int]] = None, **kw):
        super().__init__(*args, **kw)
        self.disagg = tuple(disagg) if disagg is not None else None
        self.stats.update(admitted=0, completed=0, prefills=0,
                          active_lane_steps=0)
        self._slot_caches = None
        self._draft_slot_caches = None
        eligible = paged_eligible(self.model.cfg, self.plan)
        if paged == "auto":
            paged = eligible
        elif paged and not eligible:
            raise ValueError(
                "paged KV needs an all-attention, unwindowed, causal model "
                "(recurrent state and ring buffers have no paged analogue) "
                "under no plan, a mode='serve' plan, or a throughput "
                "(exact=False) serve_pipeline plan (the exact pipeline "
                "streams the dense slot path)")
        self.paged = bool(paged)
        # throughput pipeline (exact=False serve_pipeline): stage-local
        # arenas + lane groups, one group per stage schedule offset
        self._stage_n = 0
        if (self.plan is not None and self.plan.mode == "serve_pipeline"
                and not getattr(self.plan, "exact", True)):
            if spec_config:
                raise ValueError(
                    "spec_config does not compose with the request-skewed "
                    "serve_pipeline plan (the spec program has no skewed "
                    "schedule); serve speculation from a mode='serve' plan")
            self._stage_n = self.plan.mesh.shape[self.plan.axes.stage]
            if self.max_batch % self._stage_n:
                raise ValueError(
                    f"request-skewed serve_pipeline splits the batch into "
                    f"one lane group per stage: max_batch={self.max_batch} "
                    f"must be a multiple of the stage count "
                    f"{self._stage_n}")
            self.sched.set_lane_groups(self._stage_n)
        assert kv_dtype in ("bf16", "int8"), kv_dtype
        if kv_dtype == "int8" and not self.paged:
            raise ValueError(
                "kv_dtype='int8' needs the paged KV pool (quantized dense "
                "slot rows are not implemented); this model/config fell "
                "back to dense slots")
        self.kv_dtype = kv_dtype
        self.kv: Optional[KVManager] = None
        self.spec = bool(spec_config)
        if self.spec and not self.paged:
            raise ValueError(
                "spec_config needs the paged KV pool: the draft arena and "
                "the batched verify both address KV through page tables")
        if self.paged:
            self.page_size = page_size
            # whole-page capacity: gathered paged layout == dense slot row
            self.cache_len = -(-self.cache_len // page_size) * page_size
            self.executor.cache_len = self.cache_len
            self.max_pages = self.cache_len // page_size
            if num_pages is None:  # default: dense table capacity + trash
                num_pages = self.max_batch * self.max_pages + 1
            draft_num_pages = 0
            if self.spec:
                # draft arena default: position parity with the target —
                # every target page the pool can hand a lane has a draft
                # twin (kv_manager.spec_pool_split sizes both from one
                # HBM byte budget when the caller wants byte parity)
                self.spec_k = int(spec_config.get("spec_k", 4))
                assert self.spec_k >= 1
                self.draft_model: Model = spec_config["draft_model"]
                draft_num_pages = int(spec_config.get("draft_num_pages")
                                      or num_pages)
                self.executor.set_draft(self.draft_model,
                                        spec_config["draft_params"])
                self.sched.set_spec(self.spec_k)
                self._spec_warm = False
                self._tpos = [0] * self.max_batch   # target positions
                self._dpos = [0] * self.max_batch   # draft positions
                self.stats.update(spec_dispatches=0, spec_draft_steps=0,
                                  spec_accepted=0, spec_proposed=0,
                                  spec_draft_prefills=0,
                                  spec_catchup_tokens=0)
            self.kv = KVManager(num_pages, page_size, self.max_batch,
                                self.max_pages,
                                draft_num_pages=draft_num_pages,
                                shards=self._stage_n or 1)
            self.max_hit_suffix = (max(self.buckets)
                                   if max_hit_suffix is None
                                   else max_hit_suffix)
            self._ladder_warm = False
            self.stats.update(prefix_hits=0, prefix_hit_tokens=0,
                              preemptions=0, pages_in_use=0, pages_peak=0)
        if self.disagg is not None:
            self._init_disagg()

    def _init_disagg(self) -> None:
        """Disaggregated prefill/decode pools (`disagg=(P, D)`): devices
        [0, P) become the prefill pool, [P, P+D) the decode pool.  The
        prefill pool owns bucketed prefill + a transient staging arena;
        completed pages ship into the decode arena (executor.ship_pages)
        and ownership moves with them.  One radix tree — the decode
        pool's — spans both: it indexes decode-arena pages only, so a
        prefix hit admits without touching the prefill pool at all."""
        import jax
        p, d = self.disagg
        if not self.paged:
            raise ValueError(
                "disagg needs the paged KV pool: page shipping is the "
                "ownership-handoff mechanism (dense slot rows have no "
                "transferable unit)")
        if self.plan is not None:
            raise ValueError(
                "disagg does not compose with a ClusterPlan (the plan "
                "already owns device placement); pick one")
        if self.spec:
            raise ValueError(
                "disagg does not compose with spec_config (the draft "
                "arena has no shipping path yet)")
        devices = jax.devices()
        if p < 1 or d < 1 or p + d > len(devices):
            raise ValueError(
                f"disagg={self.disagg}: needs prefill >= 1, decode >= 1, "
                f"prefill+decode <= {len(devices)} host devices")
        self.executor.set_disagg(devices[:p], devices[p:p + d])
        # staging KV: one admission in flight, so max_pages + trash always
        # covers the export; its ledgers see the same actual-freed
        # accounting as the decode pool's
        self.kv_prefill = KVManager(self.max_pages + 1, self.page_size, 1,
                                    self.max_pages)
        self._prefill_arena = None
        self.stats.update(shipped_pages=0, shipped_bytes=0,
                          ship_dispatches=0)
        # queue split: the radix peek classifies pending requests into the
        # decode-ingest queue (hit: admits decode-side, zero transfers)
        # vs the prefill queue, and drives the pool-aware occupancy
        # signals the fleet router reads (scheduler.set_disagg).
        # prefill_chunk=max_batch keeps in-process admission
        # work-conserving — the pools drain sequentially on this host, so
        # throttling colds below free-slot capacity only delays them; the
        # SJF ordering alone is what shields steady short traffic from a
        # long-prompt burst.  A cross-host prefill pool with real
        # per-cycle capacity would lower the chunk to its worker count.
        self.sched.set_disagg(
            lambda r: self.kv.peek_hit(np.asarray(r.effective_prompt())),
            prefill_chunk=self.max_batch)

    pool = property(lambda self: self.kv.pool)
    prefix_cache = property(lambda self: self.kv.prefix_cache)
    _lane_pages = property(lambda self: self.kv._lane_pages)

    def hit_stats(self) -> dict:
        """Prefix-reuse summary with the derived hit rate — the per-replica
        figure the fleet router aggregates (serving/replica.py); dense
        engines report zeros (no radix tree to hit)."""
        s = self.stats
        admitted = s.get("admitted", 0)
        hits = s.get("prefix_hits", 0)
        return {"admitted": admitted, "prefix_hits": hits,
                "prefix_hit_tokens": s.get("prefix_hit_tokens", 0),
                "prefix_hit_rate": hits / admitted if admitted else 0.0}

    def kv_page_bytes(self) -> int:
        """Per-device HBM bytes one arena page costs at this engine's
        kv_dtype (stage-sharded arenas hold 1/stages of the stack)."""
        return kv_page_bytes(self.model.cfg, self.page_size, self.kv_dtype,
                             shards=self.kv.shards if self.kv else 1)

    def _admit_dense(self, r: Request, sl: int, st) -> bool:
        """Batch-1 prefill + insert into slot `sl`; TTFT paid here."""
        with span("cold_admission", self.stats, "prefill_s", rid=r.rid):
            logits, small = self._prefill([r.prompt], 1, bucket_cache=True)
            st["caches"] = self.executor.insert(st["caches"], small, sl)
            self.stats["prefills"] += 1
            self.stats["admitted"] += 1
            self._first_token(r, int(self._greedy_next(logits)[0]))
        if not r.done:
            with span("admit_lane", rid=r.rid):
                self.executor.admit_lane(st, sl, r.tokens_out[-1], r.eos_id,
                                         r.remaining())
        return True

    def _admit_paged(self, r: Request, sl: int, st) -> bool:
        """Radix hit -> reuse shared pages, suffix rides the forced-token
        queue; cold -> bucket prefill scattered into owned pages + prompt
        registered.  False = pool can't cover it (nothing held)."""
        prompt = r.effective_prompt()
        grant = self.kv.admit(prompt, r.remaining(), self.max_hit_suffix,
                              spec_margin=getattr(self, "spec_k", 0)
                              if self.spec else 0)
        if grant is None:
            return False
        self.stats["admitted"] += 1
        if grant.hit_len:
            suffix = prompt[grant.hit_len:]
            with span("admit_hit", rid=r.rid):
                self.executor.admit_hit(st, sl, grant.pt_row, grant.hit_len,
                                        grant.reset)
            with span("admit_lane", rid=r.rid):
                self.executor.admit_lane_paged(
                    st, sl, int(suffix[0]), r.eos_id, r.remaining(),
                    suffix[1:], len(suffix) - 1)
            self.sched.lane_forced[sl] = len(suffix) - 1
            self.stats["prefix_hits"] += 1
            self.stats["prefix_hit_tokens"] += int(grant.hit_len)
            r.t_admitted = time.perf_counter()
        else:
            with span("cold_admission", self.stats, "prefill_s", rid=r.rid):
                logits, small = self._prefill([prompt], 1, bucket_cache=True)
                bucket = bucket_len(len(prompt), self.buckets, lane=8)
                n_wp = min(self.kv.pages_for(bucket), len(grant.pages))
                with span("admit_cold", rid=r.rid):
                    if self.disagg is not None:
                        self._ship_cold(st, sl, small, grant, prompt, bucket,
                                        n_wp)
                    else:
                        self.executor.admit_cold(
                            st, sl, small, grant.pt_row, len(prompt),
                            grant.reset,
                            np.asarray(grant.pages[:n_wp], np.int32), bucket)
                self.stats["prefills"] += 1
                self.kv.register_prefix(prompt, grant.pages)
                self._first_token(r, int(self._greedy_next(logits)[0]))
            self.sched.lane_forced[sl] = 0
            if not r.done:
                with span("admit_lane", rid=r.rid):
                    self.executor.admit_lane_paged(
                        st, sl, r.tokens_out[-1], r.eos_id, r.remaining(),
                        np.zeros((0,), np.int32), 0)
        self.kv.commit(sl, grant)
        if self.spec and not r.done:
            self._admit_draft(r, sl, st, grant, prompt)
        self.stats["pages_in_use"] = self.kv.pages_in_use
        self.stats["pages_peak"] = max(self.stats["pages_peak"],
                                       self.kv.pages_in_use)
        return True

    def _ship_cold(self, st, sl: int, small, grant, prompt, bucket: int,
                   n_wp: int) -> None:
        """The disaggregated ownership handoff for one cold admission.

        The request is *prefill-owned* while its bucket cache scatters
        into staging pages on the prefill pool's arena, then
        *decode-owned* once `ship_pages` lands those pages in the lane's
        granted decode pages.  admit_hit first sentinels every granted
        page and points the lane's table row at them, so the shipped page
        contents (bitwise what admit_cold would have written, including
        partial-page sentinel kpos and int8 scale planes) arrive into an
        arena state identical to colocated serving's."""
        src = self.kv_prefill.stage_export(n_wp)
        self._prefill_arena = self.executor.prefill_admit(
            self._prefill_arena, small, src.pt_row, len(prompt), src.reset,
            np.asarray(src.pages, np.int32), bucket)
        self.executor.admit_hit(st, sl, grant.pt_row, len(prompt),
                                grant.reset)
        self.executor.ship_pages(self._prefill_arena, st, src.pages,
                                 grant.pages[:n_wp])
        self.kv_prefill.finish_export(src.pages)
        self.stats["ship_dispatches"] += 1
        self.stats["shipped_pages"] += n_wp
        self.stats["shipped_bytes"] += n_wp * self.kv_page_bytes()

    def _admit_draft(self, r: Request, sl: int, st, grant, prompt) -> None:
        """Bring the lane's draft cache to the target's position: a cold
        lane prefills the full effective prompt on the draft model, a
        prefix-hit lane prefills prompt[:hit_len] (page-aligned, so the
        remaining suffix ingests in lockstep through the spec program's
        forced queue — the draft has no radix tree to hit on)."""
        plen = grant.hit_len if grant.hit_len else len(prompt)
        small, bucket = self.executor.draft_prefill_prompts(
            [prompt[:plen]], 1)
        n_wp = min(self.kv.draft_pool.pages_for(bucket),
                   len(grant.draft_pages))
        self.executor.admit_cold_draft(
            st, sl, small, grant.draft_pt_row, plen, grant.draft_reset,
            np.asarray(grant.draft_pages[:n_wp], np.int32), bucket)
        self._tpos[sl] = self._dpos[sl] = int(plen)
        self.sched.reset_lane_spec(sl)
        self.stats["spec_draft_prefills"] += 1

    @staticmethod
    def _first_token(r: Request, tok: int) -> None:
        t_now = time.perf_counter()
        r.t_admitted = t_now
        r.append_token(tok, t_now)

    def _release(self, sl: int) -> None:
        self.kv.release(sl)
        self.sched.lane_forced[sl] = 0
        if self.spec:
            self._tpos[sl] = self._dpos[sl] = 0
        self.stats["pages_in_use"] = self.kv.pages_in_use

    def _preempt(self, slots, pending, st) -> None:
        """Evict the lane with the most work left; greedy decode is
        deterministic, so the re-queued victim (usually a prefix hit on
        its own pages) continues exactly where it stopped.  The victim's
        preemption counter feeds the scheduler's cascade damping: at the
        budget it becomes victim-exempt and admission-priority."""
        sl = self.sched.victim(slots)
        if sl is None:
            return
        victim, slots[sl] = slots[sl], None
        victim.n_preempts += 1
        self.executor.park_lane(st, sl)
        self._release(sl)
        pending.append(victim)
        self.stats["preemptions"] += 1

    def _advance_mirrors(self, block: np.ndarray, slots, n: int) -> None:
        """Advance the host position mirrors by what the device consumed:
        per active lane, min(pending forced, n) swallowed positions plus
        one position per emitted token (every consumed step is one or the
        other — decode_steps and the spec block share this invariant)."""
        for i, r in enumerate(slots):
            if r is None:
                continue
            emitted = int((block[:, i] >= 0).sum())
            self._tpos[i] += min(self.sched.lane_forced[i], n) + emitted

    def _reconcile(self, toks, slots, done, n: int, t_step: float) -> None:
        block = self._fetch_block(toks, t_step)
        with span("reconcile", self.stats, "reconcile_s"):
            if self.spec:  # spec-disabled dispatch: draft lags, catchup later
                self._advance_mirrors(block, slots, n)
            self.sched.reconcile(block, slots, done, n, self.stats,
                                 time.perf_counter(), self.paged,
                                 self._release if self.paged else None)

    def _reconcile_spec(self, toks, slots, done, k: int,
                        t_step: float) -> None:
        """Spec-dispatch bookkeeping: one (k+1, B) block per dispatch;
        acceptance feedback drives the per-lane depth ladder, and the
        position mirrors advance in lockstep on both caches (the device
        rewound them together)."""
        block = self._fetch_block(toks, t_step)
        with span("reconcile", self.stats, "reconcile_s"):
            self.stats["spec_dispatches"] += 1
            self.stats["spec_draft_steps"] += k + 1
            self._advance_mirrors(block, slots, k + 1)
            for i, r in enumerate(slots):
                if r is None:
                    continue
                self._dpos[i] = self._tpos[i]  # verify + rewind: equal
                emitted = int((block[:, i] >= 0).sum())
                if emitted >= 1 and self.sched.lane_forced[i] == 0:
                    # emitted = 1 guaranteed + accepted drafts (+ bonus);
                    # forced-ingest dispatches say nothing about the draft
                    accepted = min(emitted - 1, k)
                    self.stats["spec_accepted"] += accepted
                    self.stats["spec_proposed"] += k
                    self.sched.observe_acceptance(i, accepted, k)
            self.sched.reconcile(block, slots, done, k + 1, self.stats,
                                 time.perf_counter(), self.paged,
                                 self._release)

    def _spec_catchup(self, slots, st) -> None:
        """Feed draft lanes the stream tokens the target consumed during
        spec-disabled dispatches, so the draft cache re-enters speculation
        at the target's exact position."""
        lags = [(self._tpos[i] - self._dpos[i]) if r is not None else 0
                for i, r in enumerate(slots)]
        width = max(lags)
        if width <= 0:
            return
        tokens = np.zeros((self.max_batch, width), np.int32)
        for i, r in enumerate(slots):
            if r is None or lags[i] == 0:
                continue
            stream = np.concatenate(
                [np.asarray(r.prompt, np.int32),
                 np.asarray(r.tokens_out, np.int32)])
            tokens[i, :lags[i]] = stream[self._dpos[i]:self._tpos[i]]
            self._dpos[i] = self._tpos[i]
        self.executor.draft_catchup(st, tokens,
                                    np.asarray(lags, np.int32))
        self.stats["spec_catchup_tokens"] += int(sum(lags))

    def run(self) -> List[Request]:
        """Serve until queue + slots drain; returns requests sorted by rid.
        Admission honours `Request.t_arrival` (seconds after this call)."""
        if self._slot_caches is None:
            self._slot_caches = self.executor.init_caches(
                self.paged, *((self.page_size, self.kv.num_pages,
                               self.max_pages, self.kv_dtype)
                              if self.paged else ()))
        if self.spec and self._draft_slot_caches is None:
            self._draft_slot_caches = self.executor.init_draft_caches(
                self.page_size, self.kv.draft_pool.num_pages,
                self.max_pages, self.kv_dtype)
        if self.disagg is not None and self._prefill_arena is None:
            self._prefill_arena = self.executor.init_prefill_arena(
                self.page_size, self.kv_prefill.num_pages, self.max_pages,
                self.kv_dtype)
        st = self.executor.fresh_state(
            self._slot_caches, self.paged,
            draft_caches=self._draft_slot_caches if self.spec else None)
        # programs donate the caches: drop the handles (abnormal-exit safety)
        self._slot_caches = None
        self._draft_slot_caches = None
        if self.paged and not self._ladder_warm:
            self.executor.warm_ladder(st, self.sched.horizons)
            self._ladder_warm = True
        if self.spec and not self._spec_warm:
            self.executor.warm_spec(st, self.sched.spec_ladder)
            self._spec_warm = True
        done: List[Request] = []
        pending = self.sched.take_queue()
        slots: List[Optional[Request]] = [None] * self.max_batch
        admit = self._admit_paged if self.paged else self._admit_dense
        t0 = time.perf_counter()
        for r in pending:  # latency clocks start at simulated arrival
            r.t_enqueue = max(r.t_enqueue, t0 + r.t_arrival)

        s = self.stats
        with loop_clock(s):
            while pending or any(r is not None for r in slots):
                now = time.perf_counter() - t0
                with span("loop_control"):
                    free = self.sched.order_free(
                        [i for i, r in enumerate(slots) if r is None], slots)
                with span("admission_cycle", s, "admit_s"):
                    admitted, starved = self.sched.admission_cycle(
                        pending, free, now, self.executor.warm_buckets,
                        lambda r, sl: admit(r, sl, st))
                    for r, sl in admitted:
                        pending.remove(r)
                        if r.done:  # budget of 1 / instant EOS at admission
                            done.append(r)
                            if self.paged:
                                self._release(sl)
                            free.insert(0, sl)
                            s["completed"] += 1
                        else:
                            slots[sl] = r
                    if self.sched.should_preempt(starved, now):
                        self._preempt(slots, pending, st)
                if not any(r is not None for r in slots):
                    with span("idle_wait", s, "idle_s"):
                        self.sched.idle_wait(pending, starved,
                                             time.perf_counter() - t0)
                    continue

                with span("loop_control"):
                    k = (self.sched.spec_depth(slots, starved is not None)
                         if self.spec else 0)
                    if not k:
                        n = self.sched.pick_horizon(
                            bool(pending), self.sched.lane_remaining(slots))
                t_step = time.perf_counter()
                if k:
                    self._spec_catchup(slots, st)
                    toks = self.executor.spec_decode(st, k)
                    self._reconcile_spec(toks, slots, done, k, t_step)
                else:
                    toks = self.executor.decode(st, n, self.paged)
                    self._reconcile(toks, slots, done, n, t_step)

        if self.paged:
            self.kv.assert_drained()
            if self.disagg is not None:
                # exports are transient: every staged page was returned by
                # finish_export before its admission completed
                self.kv_prefill.assert_drained()
        self._slot_caches = st["caches"]
        self._draft_slot_caches = st.get("draft_caches")
        return sorted(done, key=lambda r: r.rid)


def __getattr__(name):  # PEP 562: WaveEngine (serving/wave.py) subclasses
    if name == "WaveEngine":  # EngineBase — lazy both ways, no import cycle
        from repro.serving.wave import WaveEngine
        return WaveEngine
    raise AttributeError(name)


ServingEngine = ContinuousBatchingEngine
