"""Smoke run of the serving main path on TPU.

Serves smollm-135m at its published widths (30 layers, d_model 576, 9
query / 3 kv heads, vocab 49152; random weights from seed 0) through the
normal entry point, `repro.launch.serve.main`, with its default
`--plan serve` placement and paged bf16 KV arena:

  python chip_smoke.py               one chip: a cold-prefill pass (poisson
                                     stream), a prefix-hit pass
                                     (shared-prefix stream) and a check of
                                     the Pallas decode kernel against the
                                     jnp route on the same chip
  python chip_smoke.py --four-chips  a four-chip host: the shared-prefix
                                     stream on one chip, on a 1x4
                                     gather-form TP mesh and on four
                                     one-chip replicas behind the router;
                                     the token streams must be identical
                                     per request

It exits nonzero, and prints no result line, when JAX finds no TPU or any
check fails.  The other lines it prints (compile seconds, pass wall
times, engine stats) are smoke facts, not measurements.  The last line of
stdout is one JSON object:
{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.core.packing import bucket_len  # noqa: E402
from repro.kernels import ops as kops  # noqa: E402
from repro.launch import serve  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.models.transformer import init_params, make_model  # noqa: E402
from repro.serving.executor import Executor  # noqa: E402

ARCH = "smollm-135m"
SEED = 0
SERVE_ARGS = ["--arch", ARCH, "--max-batch", "8", "--max-new", "32",
              "--seed", str(SEED)]
# the serve CLI's bucket ladder and the engine's default decode headroom
BUCKETS = (16, 32, 64, 128)
CACHE_LEN = 128 + 64
PAGE = 16
# Kernel route vs jnp route on one chip.  Both run the bf16 model; they
# differ in the decode attention only (the kernel keeps f32 scores, the
# jnp oracle rounds them to bf16), and that difference passes through 30
# layers before the logits.  Tolerance on max |logit difference|,
# relative to the largest |logit| of the jnp route: 2^-4.
LOGIT_RTOL = 2.0 ** -4


class Compiles:
    """Counts backend compiles and persistent-cache hits of this process."""

    def __init__(self):
        self.seconds = 0.0
        self.n = 0
        self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs
            self.n += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def line(self) -> str:
        return (f"{self.seconds:.1f}s backend compile over {self.n} "
                f"programs, {self.hits} persistent-cache hits")


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SystemExit(f"chip_smoke: FAILED: {what}")


def serve_pass(name: str, compiles: Compiles, *extra: str):
    t0, c0 = time.perf_counter(), compiles.seconds
    done, engine = serve.main(SERVE_ARGS + list(extra))
    wall = time.perf_counter() - t0
    print(f"chip_smoke: {name}: wall {wall:.1f}s (compile "
          f"{compiles.seconds - c0:.1f}s of it), {len(done)} requests")
    short = [r.rid for r in done if len(r.tokens_out) != r.max_new_tokens]
    check(len(done) == 16 and not short,
          f"{name}: requests short of their budget: {short}")
    return {r.rid: list(r.tokens_out) for r in done}, engine


def decode_lowered_text(engine) -> str:
    """StableHLO of the engine's paged decode program as it serves."""
    ex = engine.executor
    st = ex.fresh_state(engine._slot_caches, True)
    with ex._ctx():
        return ex.decode_fn(max(engine.sched.horizons), True).lower(
            ex.params, st["caches"], st["cur"], st["active"], st["eos"],
            st["budget"], st["forced"], st["flen"], st["fptr"]).as_text()


def route_probe(impl: str, n_steps: int = 16):
    """Prefill logits, the first paged-decode step's logits and an
    n-step greedy stream for four prompts, every program traced under one
    kops route.  A fresh Executor per call keeps the routes from sharing
    a traced program."""
    cfg = get_config(ARCH)
    model = make_model(cfg, remat=False)
    lens = (21, 40, 64, 100)
    max_pages = CACHE_LEN // PAGE
    rng = np.random.default_rng(SEED)
    with kops.pinned_impl(impl):
        ex = Executor(model, init_params(cfg, jax.random.PRNGKey(SEED)),
                      max_batch=len(lens), cache_len=CACHE_LEN,
                      buckets=BUCKETS)
        st = ex.fresh_state(
            ex.init_caches(True, PAGE, len(lens) * max_pages + 1, max_pages),
            True)
        prefill = []
        for lane, n in enumerate(lens):
            prompt = rng.integers(0, cfg.vocab_size, n).astype(np.int32)
            logits, small = ex.prefill_prompts([prompt], 1,
                                               bucket_cache=True)
            pages = np.arange(1 + lane * max_pages, 1 + (lane + 1) * max_pages,
                              dtype=np.int32)
            bucket = bucket_len(n, BUCKETS, lane=8)
            ex.admit_cold(st, lane, small, pages, n, pages,
                          pages[:-(-bucket // PAGE)], bucket)
            prefill.append(np.asarray(logits[0], np.float32))
            ex.admit_lane_paged(st, lane, int(np.argmax(prefill[-1])), -1,
                                n_steps + 1, np.zeros((0,), np.int32), 0)
        step, _ = jax.jit(model.decode_step)(ex.params, st["caches"],
                                             st["cur"], st["active"])
        step = np.asarray(step, np.float32)
        toks = np.asarray(ex.decode(st, n_steps, True))
    return np.stack(prefill), step, toks


def one_chip(compiles: Compiles) -> None:
    _, engine = serve_pass("poisson pass (cold prefill)", compiles,
                           "--stream", "poisson")
    print(f"chip_smoke: poisson stats {engine.stats}")
    check(engine.stats["prefills"] > 0, "poisson pass ran no prefill")
    hlo = decode_lowered_text(engine)
    n_kernels = hlo.count("tpu_custom_call")
    print(f"chip_smoke: decode program holds {n_kernels} tpu_custom_call")
    check(n_kernels > 0, "the decode program bypasses the Pallas kernel")

    _, engine = serve_pass("shared-prefix pass (prefix hits)", compiles,
                           "--stream", "shared-prefix")
    print(f"chip_smoke: shared-prefix stats {engine.stats}")
    check(engine.stats["prefix_hits"] > 0,
          "shared-prefix pass recorded no prefix hit")

    t0 = time.perf_counter()
    pre_k, dec_k, toks_k = route_probe("pallas")
    pre_r, dec_r, toks_r = route_probe("ref")
    print(f"chip_smoke: route probe wall {time.perf_counter() - t0:.1f}s")
    for what, got, want in (("prefill", pre_k, pre_r),
                            ("first decode step", dec_k, dec_r)):
        err = float(np.max(np.abs(got - want)))
        scale = float(np.max(np.abs(want)))
        check(np.all(np.isfinite(got)), f"{what} logits not finite")
        print(f"chip_smoke: {what} logits, kernel vs jnp route: max |diff| "
              f"{err:.4g}, max |logit| {scale:.4g}, limit "
              f"{LOGIT_RTOL * scale:.4g}")
        check(err <= LOGIT_RTOL * scale,
              f"{what} logits: kernel route differs from the jnp route")
    match = float(np.mean(toks_k == toks_r))
    print(f"chip_smoke: greedy tokens, kernel vs jnp route: match rate "
          f"{match:.4f} over {toks_k.size} tokens")


def first_divergence(got: dict, want: dict):
    """(request id, step) of the first token that differs, else None."""
    for rid in sorted(want):
        a, b = got.get(rid, []), want[rid]
        for i in range(max(len(a), len(b))):
            if i >= len(a) or i >= len(b) or a[i] != b[i]:
                return rid, i
    return None


def four_chips(compiles: Compiles) -> None:
    """Both comparisons run and print before either can fail the run."""
    check(len(jax.devices()) >= 4, "--four-chips needs four devices")
    stream = ["--stream", "shared-prefix"]
    want, _ = serve_pass("one chip (mesh 1,1)", compiles, *stream,
                         "--mesh", "1,1")
    got_tp, _ = serve_pass("gather-form TP (mesh 1,4)", compiles, *stream,
                           "--mesh", "1,4")
    div_tp = first_divergence(got_tp, want)
    print(f"chip_smoke: TP 1x4 vs one chip: first divergent "
          f"(request, step) {div_tp}")
    got_rep, fleet = serve_pass("four one-chip replicas", compiles, *stream,
                                "--replicas", "4")
    homes = {d for rep in fleet.replicas
             for d in jax.tree.leaves(rep.engine.executor.params)[0].devices()}
    div_rep = first_divergence(got_rep, want)
    print(f"chip_smoke: 4 replicas vs one chip: first divergent "
          f"(request, step) {div_rep}; replica devices "
          f"{sorted(d.id for d in homes)}")
    check(len(homes) == 4, f"replicas share devices: {homes}")
    check(div_tp is None, f"TP 1x4 streams differ from one chip at {div_tp}")
    check(div_rep is None,
          f"replica streams differ from one chip at {div_rep}")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip comparison")
    args = ap.parse_args()
    enable_compile_cache()
    dev = jax.devices()[0]
    check(dev.platform == "tpu", f"no TPU: JAX runs on {dev.platform}")
    check(kops.default_impl() == "pallas",
          f"kernels would run as {kops.default_impl()!r}, not 'pallas'")
    print(f"chip_smoke: device {dev.device_kind} x {len(jax.devices())}, "
          f"jax {jax.__version__}")
    compiles = Compiles()
    t0 = time.perf_counter()
    (four_chips if args.four_chips else one_chip)(compiles)
    print(f"chip_smoke: total wall {time.perf_counter() - t0:.1f}s; "
          f"{compiles.line()}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
