"""The system under test, as the benchmark drives it.

Everything the benchmark knows of the program but the model (whose
config and weights the configuration's family module makes) is here:
the check of the weights' layout, the engine it builds (as
`launch/serve.py` does for `--plan none`: paged bfloat16 arena, one
device, the engine's own page size, decode horizon, page count and
kernel route), the request type it submits, and the instance methods
it wraps for host spans and for the per-dispatch work count of a traced
run.  Wrapping sets attributes on the instances only; no file of the
program is touched.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Callable, List

import jax
import numpy as np

from repro.configs.base import ModelConfig
from repro.kernels import ops as kops
from repro.models.transformer import init_params, make_model
from repro.serving.engine import ContinuousBatchingEngine
from repro.serving.scheduler import Request

PAGE = 16  # the engine's default page size, used only to warm its programs
# the executor's paged decode program, as the device trace names it
DECODE_PROGRAM = r"pfn"


@dataclass(eq=False)
class TimedRequest(Request):
    """A request that keeps, for each token it is handed, the host time of
    the hand-off (`t_tokens`) and the program's stamp of the dispatch it
    came in (`blocks`: the same for every token of one block)."""
    t_tokens: List[float] = field(default_factory=list)
    blocks: List[float] = field(default_factory=list)

    def append_token(self, tok: int, now: float) -> None:
        super().append_token(tok, now)
        self.t_tokens.append(time.perf_counter())
        self.blocks.append(now)


def check_layout(mcfg: ModelConfig, params) -> None:
    """The benchmark's weights must fill the program's parameter tree
    exactly: same paths, shapes and types."""
    want = jax.eval_shape(lambda: init_params(mcfg, jax.random.PRNGKey(0)))
    got = jax.eval_shape(lambda: params)
    sig = lambda t: jax.tree_util.tree_map(  # noqa: E731
        lambda a: (a.shape, str(a.dtype)), t)
    if sig(want) != sig(got):
        raise SystemExit(f"bench: the program's parameter layout changed:\n"
                         f"program {sig(want)}\nbenchmark {sig(got)}")


def build_engine(mcfg: ModelConfig, params, max_batch: int, buckets,
                 max_decode_len: int) -> ContinuousBatchingEngine:
    engine = ContinuousBatchingEngine(
        make_model(mcfg, remat=False), params, max_batch=max_batch,
        buckets=tuple(buckets), max_decode_len=max_decode_len,
        kv_dtype="bf16")
    if not engine.paged:
        raise SystemExit("bench: the engine fell back to dense slots")
    return engine


def kernel_route() -> str:
    return kops.default_impl()


def pages(n: int) -> int:
    return -(-n // PAGE)


def bucket_of(n: int, buckets) -> int:
    return next(b for b in sorted(buckets) if n <= b)


def warm_pairs(buckets, requests):
    """Every (prefill bucket, pages written) a cold admission of these
    (prompt length, budget) requests can ask for: each as it arrives, and
    each re-admitted after preemption with part of its answer as prompt.
    A re-admission longer than the largest bucket has no prefill program
    to warm; the pool holds every lane's whole context, so none starves
    and none is preempted."""
    seen = set()
    for n, budget in requests:
        need = pages(n + budget)
        for m in range(n, min(n + budget, max(buckets) + 1)):
            b = bucket_of(m, buckets)
            seen.add((b, min(pages(b), need)))
    return sorted(seen)


def warm(engine, pairs, vocab: int, seed: int) -> None:
    """Compile every program the window will use by serving one
    single-token request per (bucket, pages) pair through the engine;
    then drop their cached prefixes so the window starts cold."""
    rng = np.random.default_rng(seed)
    for i, (b, w) in enumerate(pairs):
        n = min(b, PAGE * w - 1)
        engine.submit(TimedRequest(
            rid=-1 - i, prompt=rng.integers(0, vocab, n).astype(np.int32),
            max_new_tokens=1))
    engine.run()
    kv = engine.kv
    kv.evict_cached(kv.prefix_cache.cached_pages)


def decode_has_kernel(engine) -> bool:
    """Whether the paged decode program, as the engine serves it, holds a
    Pallas kernel (tpu_custom_call)."""
    ex = engine.executor
    st = ex.fresh_state(engine._slot_caches, True)
    with ex._ctx():
        text = ex.decode_fn(max(engine.sched.horizons), True).lower(
            ex.params, st["caches"], st["cur"], st["active"], st["eos"],
            st["budget"], st["forced"], st["flen"], st["fptr"]).as_text()
    return "tpu_custom_call" in text


# -- host spans (traced runs) ----------------------------------------------

SPANS = {  # span name -> (owner, attribute)
    "admission_cycle": ("sched", "admission_cycle"),
    "idle_wait": ("sched", "idle_wait"),
    "reconcile": ("sched", "reconcile"),
    "cold_prefill": ("executor", "prefill_prompts"),
    "admit_cold": ("executor", "admit_cold"),
    "admit_hit": ("executor", "admit_hit"),
    "admit_lane": ("executor", "admit_lane_paged"),
    "decode_dispatch": ("executor", "decode"),
    "first_token_sync": ("engine", "_greedy_next"),
}


def _owner(engine, name):
    return engine if name == "engine" else getattr(engine, name)


def install_spans(engine) -> None:
    """Wrap each method of SPANS in a profiler TraceAnnotation of its name."""
    for span, (owner, attr) in SPANS.items():
        obj = _owner(engine, owner)
        fn = getattr(obj, attr, None)
        if fn is None:
            continue

        def wrapped(*a, _fn=fn, _span=span, **k):
            with jax.profiler.TraceAnnotation(_span):
                return _fn(*a, **k)

        setattr(obj, attr, wrapped)


def install_counts(engine, on_dispatch: Callable, on_prefill: Callable) -> None:
    """Call on_dispatch(lanes, n, now) at each decode dispatch's
    reconciliation, before the block is applied, with one
    (position, steps, emitted) per lane that ran; and on_prefill(lengths)
    at each cold prefill."""
    sched, ex = engine.sched, engine.executor
    rec, pre = sched.reconcile, ex.prefill_prompts

    def reconcile(block, slots, done, n, stats, now, *a, **k):
        lanes = []
        for i, r in enumerate(slots):
            if r is None:
                continue
            forced = sched.lane_forced[i]
            pos = len(r.prompt) + len(r.tokens_out) - 1 - forced
            steps = min(n, forced + r.remaining())
            lanes.append((pos, steps, int((block[:, i] >= 0).sum())))
        on_dispatch(lanes, n, time.perf_counter())
        return rec(block, slots, done, n, stats, now, *a, **k)

    def prefill(prompts, batch, *a, **k):
        out = pre(prompts, batch, *a, **k)
        on_prefill([len(p) for p in prompts])
        return out

    sched.reconcile = reconcile
    ex.prefill_prompts = prefill
