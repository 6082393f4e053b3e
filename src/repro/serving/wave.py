"""WaveEngine: the seed's batch-synchronous scheduler, kept as the
measured baseline for `benchmarks/run.py serve_cb` — one batched prefill
per wave, decode until every member finishes, finished rows feeding
PAD_TOKEN behind the decode active mask.  Split out of engine.py so the
composition root stays thin; re-exported there for the public API.
"""
from __future__ import annotations

import time
from typing import List

import jax.numpy as jnp

# engine.py imports this module at its bottom, after EngineBase exists, so
# the circular import resolves in definition order
from repro.serving import engine as _engine
from repro.serving.executor import PAD_TOKEN
from repro.serving.scheduler import Request
from repro.serving.tracing import loop_clock, span


class WaveEngine(_engine.EngineBase):
    """Batch-synchronous baseline (docs/serving.md §wave baseline)."""

    def __init__(self, *args, **kw):
        super().__init__(*args, **kw)
        if self.plan is not None and self.plan.mode == "serve_pipeline":
            raise ValueError(
                "serve_pipeline drives the continuous engine's fixed-lane "
                "decode state; the wave baseline has no fixed batch")
        self.stats.update(waves=0)

    def run(self) -> List[Request]:
        done: List[Request] = []
        pending = self.sched.take_queue()
        t0 = time.perf_counter()
        for r in pending:  # latency clocks start at simulated arrival
            r.t_enqueue = max(r.t_enqueue, t0 + r.t_arrival)
        deadline_s = self.policy.deadline.deadline_s
        with loop_clock(self.stats):
            while pending:
                # deadline batching: launch a partial wave at the deadline
                # instead of waiting for a full batch
                with span("idle_wait", self.stats, "idle_s"):
                    while True:
                        now = time.perf_counter() - t0
                        arrived = [r for r in pending if r.t_arrival <= now]
                        if len(arrived) >= self.max_batch:
                            break
                        if len(arrived) == len(pending):
                            break  # nobody else can join: don't wait
                        if arrived and now - min(
                                r.t_arrival for r in arrived) >= deadline_s:
                            break
                        nxt = min((r.t_arrival for r in pending
                                   if r.t_arrival > now),
                                  default=float("inf"))
                        wake = min([nxt] + [r.t_arrival + deadline_s
                                            for r in arrived])
                        time.sleep(max(min(wake - now, 0.005), 0.0005))
                wave = arrived[: self.max_batch]
                for r in wave:
                    pending.remove(r)
                done += self._serve_wave(wave)
        return done

    def _serve_wave(self, wave: List[Request]) -> List[Request]:
        s = self.stats
        s["waves"] += 1
        with span("admission_cycle", s, "admit_s"):
            with span("cold_admission", s, "prefill_s"):
                logits, caches = self._prefill([r.prompt for r in wave],
                                               len(wave))
                nxt = self._greedy_next(logits)
                now = time.perf_counter()
                for i, r in enumerate(wave):
                    r.append_token(int(nxt[i]), now)
            # decode state moves to device once per wave; the fused horizon
            # loop feeds tokens back on device and ships (n, b) blocks out
            st = {"caches": caches,
                  "cur": jnp.asarray([PAD_TOKEN if r.done
                                      else r.tokens_out[-1] for r in wave],
                                     jnp.int32),
                  "active": jnp.asarray([not r.done for r in wave]),
                  "eos": jnp.asarray([r.eos_id for r in wave], jnp.int32),
                  "budget": jnp.asarray([r.remaining() for r in wave],
                                        jnp.int32)}

        while not all(r.done for r in wave):
            n = self.sched.pick_horizon(
                False, [r.remaining() for r in wave if not r.done])
            t_step = time.perf_counter()
            toks = self.executor.decode(st, n, paged=False)
            s["decode_dispatches"] += 1
            s["decode_steps"] += n
            s["device_syncs"] += 1
            block = self._fetch_block(toks, t_step)
            with span("reconcile", s, "reconcile_s"):
                self.sched.append_block(block, wave, time.perf_counter())
        return wave
