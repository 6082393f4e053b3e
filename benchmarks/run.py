"""Benchmark harness — one function per paper table/figure (§8, §9).

Prints ``name,us_per_call,derived`` CSV rows.  All measurements are CPU
wall-clock of the jnp integer path (the kernels' oracle math); the TPU
projection (table5/section9 analogues) comes from the roofline module, which
is exactly the paper's §9 methodology (measure proof-of-concept, project
analytically onto the target part).

  table1: per-encoder latency components T (and fitted X) vs sequence length
  table2: Eq.1 full-model (12-encoder pipeline) latency estimates
  table3: padded vs no-padding latency (GLUE avg len 38, paper's headline)
  table4: throughput, padded vs packed (inferences/s)
  table5: comparison row vs the paper's published accelerator numbers
  fig15 : "resource utilization" analogue — Cluster-Builder kernel counts
          and routing-table entries (2N-1 vs N^2)
  sec9  : v5e int8 roofline estimate of encoder latency (Versal analogue)
  gmi   : collective byte models — composed vs fused vs gateway-hierarchical
  serve_cb: wave vs continuous-batching serving throughput + TTFT (§8.2)
  serve_paged: paged KV + radix prefix reuse vs dense slots at equal KV HBM
          (also via ``serve_cb --shared-prefix``)
  serve_quant: int8 KV-cache pages vs bf16 paged at equal KV HBM + greedy
          token-match rate (also via ``serve_cb --kv-dtype int8``)
  serve_spec: greedy speculative decoding (draft lookahead + one batched
          verify) vs the plain fused-scan engine on a decode-bound stream,
          with lossless token-match gating (also via ``serve --draft-config``)
  serve_throughput: exact=False serve_pipeline (request-skewed schedule +
          stage-local KV arenas) vs the exact drained pipeline on the
          forced multi-device host mesh, token streams gated by the 0.98
          match band (also via ``serve --plan serve_pipeline --no-exact``)

Run everything with no args, or a subset: ``python benchmarks/run.py serve_cb``.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Dict, List

import numpy as np

import jax
import jax.numpy as jnp

SEQ_LENS = (1, 2, 4, 8, 16, 32, 64, 128)
ROWS: List[str] = []


def row(name: str, us: float, derived: str = "") -> None:
    line = f"{name},{us:.1f},{derived}"
    ROWS.append(line)
    print(line)


def _median_time(fn, *args, reps: int = 3) -> float:
    fn(*args)  # compile
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts))


def _one_layer_setup():
    from repro.configs import get_config
    from repro.models import ibert as ib

    cfg = get_config("ibert-base")
    cfg1 = dataclasses.replace(cfg, n_layers=1, max_seq_len=128)
    key = jax.random.PRNGKey(0)
    params = ib.init_ibert_params(cfg1, key)
    toks = jax.random.randint(key, (1, 128), 0, cfg1.vocab_size)
    act = ib.calibrate(params, cfg1, toks)
    qp = ib.quantize_ibert(params, cfg1, act)
    return cfg1, qp


def table1_encoder_latency(state: Dict) -> None:
    """Paper Table 1 analogue: one-encoder latency T per sequence length;
    X fitted at the paper's ratio (X ~= 0.53 T at seq 128, §9)."""
    from repro.models import ibert as ib

    cfg1, qp = _one_layer_setup()
    fwd = jax.jit(
        lambda t: ib.ibert_int_forward(qp, cfg1, t, impl="ref").values,
        static_argnames=())
    t_by_seq = {}
    for s in SEQ_LENS:
        toks = jax.random.randint(jax.random.PRNGKey(s), (1, s), 0,
                                  cfg1.vocab_size)
        f = jax.jit(lambda t: ib.ibert_int_forward(
            qp, cfg1, t, impl="ref").values)
        t_by_seq[s] = _median_time(f, toks)
        row(f"table1_encoder_T_seq{s}", t_by_seq[s] * 1e6,
            f"X_est={0.5325 * t_by_seq[s] * 1e6:.1f}us")
    state["t_by_seq"] = t_by_seq


def table2_full_model_eq1(state: Dict) -> None:
    """Paper Table 2 analogue: Eq.1 with L=12 encoders, d=1.1us hop."""
    from repro.core.latency_model import StageTiming, total_latency

    t_by_seq = state["t_by_seq"]
    est = {}
    for s, t in t_by_seq.items():
        est[s] = total_latency(StageTiming(T=t, X=0.5325 * t, d=1.1e-6), 12)
        row(f"table2_ibert12_eq1_seq{s}", est[s] * 1e6,
            "T+(L-1)(X+d), L=12")
    state["eq1"] = est


def table3_padding_vs_nopadding(state: Dict) -> None:
    """Paper Table 3 analogue: GLUE avg len 38 unpadded vs padded-to-128."""
    from repro.core.packing import bucket_len

    t_by_seq = state["t_by_seq"]
    padded = t_by_seq[128]
    bucket = bucket_len(38, buckets=SEQ_LENS)  # -> 64
    nopad = t_by_seq[bucket]
    row("table3_latency_padded128", padded * 1e6, "per encoder")
    row("table3_latency_nopad_len38", nopad * 1e6,
        f"bucket={bucket}, speedup={padded / nopad:.2f}x "
        f"(paper: 7.19/2.58=2.79x)")
    state["padded"], state["nopad"] = padded, nopad


def table4_throughput(state: Dict) -> None:
    """Paper Table 4/5 analogue: pipeline steady-state throughput = 1/T."""
    from repro.core.latency_model import StageTiming, throughput

    thr_pad = throughput(StageTiming(T=state["padded"], X=0, d=0))
    thr_nopad = throughput(StageTiming(T=state["nopad"], X=0, d=0))
    row("table4_throughput_padded", 1e6 / thr_pad,
        f"{thr_pad:.1f} inf/s")
    row("table4_throughput_nopad", 1e6 / thr_nopad,
        f"{thr_nopad:.1f} inf/s, gain {thr_nopad / thr_pad:.2f}x "
        "(paper: no-padding 6802 vs 4121 = 1.65x)")


def table5_accelerator_comparison(state: Dict) -> None:
    """Paper Table 3/5 comparison row: our v5e roofline estimate vs the
    paper's published numbers (T4 1.66ms, A100 0.77ms, NPE 13.96ms,
    paper-FPGA 2.58ms no-padding batch-1 latency)."""
    est = state.get("v5e_latency")
    if est is None:
        sec9_v5e_estimate(state)
        est = state["v5e_latency"]
    for name, ms in (("NVIDIA_T4", 1.66), ("NVIDIA_A100", 0.77),
                     ("NPE_FPGA", 13.96), ("paper_6FPGA_nopad", 2.58)):
        row(f"table5_published_{name}", ms * 1e3, "paper-reported")
    row("table5_ours_v5e_roofline", est * 1e6,
        f"speedup vs A100 {0.77e-3 / est:.2f}x (estimate)")


def fig15_cluster_resources(state: Dict) -> None:
    """Fig. 15 analogue: per-cluster kernel counts & routing-table sizes."""
    from repro.configs import get_config
    from repro.core.cluster_builder import build_topology

    for arch in ("ibert-base", "deepseek-coder-33b", "moonshot-v1-16b-a3b"):
        topo = build_topology(get_config(arch))
        kmax = max(len(c.kernels) for c in topo.clusters)
        row(f"fig15_{arch}_kernels_per_cluster", kmax,
            f"clusters={len(topo.clusters)}, total={topo.total_kernels}, "
            f"routes/device={topo.routing_entries_per_device()} "
            f"(flat would be {topo.routing_entries_flat()})")


def sec9_v5e_estimate(state: Dict) -> None:
    """§9 analogue: analytic projection of the I-BERT encoder onto TPU v5e
    int8 (the paper does this for Versal AIEs and lands at 860us vs A100's
    770us)."""
    from repro.configs import get_config
    from repro.roofline.analysis import HBM_BW, PEAK_FLOPS_INT8

    cfg = get_config("ibert-base")
    s, d, f = 128, cfg.d_model, cfg.d_ff
    per_layer_flops = 2 * s * d * (3 * d) + 2 * s * s * d * 2 \
        + 2 * s * d * d + 2 * s * d * f * 2
    total_flops = per_layer_flops * cfg.n_layers
    weight_bytes = cfg.n_layers * (4 * d * d + 2 * d * f)  # int8
    compute_s = total_flops / PEAK_FLOPS_INT8
    memory_s = weight_bytes / HBM_BW
    est = max(compute_s, memory_s)
    state["v5e_latency"] = est
    row("sec9_v5e_ibert_estimate", est * 1e6,
        f"compute={compute_s * 1e6:.1f}us mem={memory_s * 1e6:.1f}us "
        f"(paper Versal est: 860us, A100: 770us)")


def gmi_collective_models(state: Dict) -> None:
    """§4/§5 analogue: link-byte models for a 1 MiB payload per device.

    composed AllReduce (Reduce->Broadcast via root, the paper's composition)
    vs fused ring vs gateway-hierarchical across 2 pods."""
    size = 2 ** 20
    n_intra, n_pods = 256, 2
    composed = 2 * size * n_intra  # root receives N, then sends N copies
    ring = 2 * size * (n_intra - 1) / n_intra  # reduce-scatter + all-gather
    flat_inter = 2 * size * (n_intra * n_pods - 1) / (n_intra * n_pods)
    gateway = ring + (size / n_intra) * 2  # intra RS/AG + leader exchange
    row("gmi_allreduce_composed_bytes", composed / 1e3,
        "bytes(KB) at root link — the paper-faithful Gather->Bcast")
    row("gmi_allreduce_ring_bytes", ring / 1e3, "fused 1-pod ring")
    row("gmi_allreduce_flat_2pod_bytes", flat_inter / 1e3,
        "flat 512-chip ring: every step crosses the pod boundary")
    row("gmi_allreduce_gateway_2pod_bytes", gateway / 1e3,
        f"hierarchical: inter-pod carries 1/{n_intra} of payload "
        "(the clusters-of-clusters gateway rule)")


def bench_int8_kernels(state: Dict) -> None:
    """Kernel microbench: int8 GEMM + i-ops wall time (interpret/oracle)."""
    from repro.kernels import ops

    rng = np.random.default_rng(0)
    a = jnp.asarray(rng.integers(-127, 128, (128, 768)), jnp.int8)
    b = jnp.asarray(rng.integers(-127, 128, (768, 768)), jnp.int8)
    t = _median_time(lambda: ops.int8_matmul(
        a, b, jnp.float32(0.01), jnp.float32(0.01), impl="ref"))
    row("kernel_int8_matmul_128x768x768", t * 1e6, "oracle path")
    x = jnp.asarray(rng.integers(-2047, 2047, (128, 128)), jnp.int32)
    t = _median_time(lambda: ops.i_softmax(x, jnp.float32(0.01), impl="ref"))
    row("kernel_i_softmax_128x128", t * 1e6, "")


def serve_cb(state: Dict) -> None:
    """§8.2 analogue: wave vs continuous-batching scheduling on a mixed
    prompt-length / mixed decode-budget request stream, plus the fused
    decode fast path (horizon-n `Model.decode_steps`) against the
    one-dispatch-per-token scheduler (the PR 1 engine) at equal outputs.

    All engines run *dense slot* caches here so the comparison isolates
    scheduling (waves vs slots vs horizon) exactly as before the paged
    pool landed — the paged-vs-dense measurement is `serve_paged`
    (`--shared-prefix`), which pins one impl for its stream-equality
    assertion."""
    import jax as _jax
    from repro.configs import get_config
    from repro.models.transformer import init_params, make_model
    from repro.serving.engine import ContinuousBatchingEngine, WaveEngine
    from repro.serving.stream import poisson_requests, replay

    cfg = get_config("smollm-135m").reduced()
    model = make_model(cfg, remat=False)
    params = init_params(cfg, _jax.random.PRNGKey(0))
    # decode-bound budgets (the regime the fused path targets) on a hot
    # Poisson ingress — the paper's line-rate feed, where waves also pay
    # their deadline-batching idle time
    stream = poisson_requests(np.random.default_rng(0), 24, cfg.vocab_size,
                              len_range=(4, 28), budgets=(32, 97), rate=400.0)

    results, metrics, streams, predicted = {}, {}, {}, {}
    setups = (
        ("wave", WaveEngine, {}),
        ("cb_step", ContinuousBatchingEngine,
         {"decode_horizon": 1, "paged": False}),
        ("cb", ContinuousBatchingEngine, {"paged": False}),
    )
    for name, cls, kw in setups:
        eng = cls(model, params, max_batch=4, buckets=(16, 32),
                  max_decode_len=96, **kw)
        replay(eng, stream, warmup=False)  # compile pass
        steps0 = eng.stats["decode_steps"]
        disp0 = eng.stats["decode_dispatches"]
        passes = []  # median of 3 measured passes (CPU box is noisy)
        for _ in range(3):
            passes.append(replay(eng, stream, warmup=False))
        done, wall, tok_s, ttft = sorted(passes, key=lambda p: p[1])[1]
        if name == "cb":  # the predicted-vs-measured stamp (perf.yml band)
            predicted["cb"] = _predicted_entry(
                _calibrate_engine(eng), eng, done, tok_s)
        results[name] = tok_s
        streams[name] = {r.rid: tuple(r.tokens_out) for r in done}
        toks = sum(len(r.tokens_out) for r in done)
        disp_tok = (eng.stats["decode_dispatches"] - disp0) / 3 / toks
        metrics[name] = {
            "tok_s": round(tok_s, 2),
            "ttft_p50_ms": round(float(np.percentile(ttft, 50)), 3),
            "ttft_p95_ms": round(float(np.percentile(ttft, 95)), 3),
            "dispatches_per_token": round(disp_tok, 4),
            "decode_horizon": eng.decode_horizon,
        }
        row(f"serve_{name}_per_token", wall / toks * 1e6,
            f"{tok_s:.1f}tok/s ttft_p50={np.percentile(ttft, 50):.1f}ms "
            f"ttft_p95={np.percentile(ttft, 95):.1f}ms "
            f"disp/tok={disp_tok:.3f} "
            f"decode_steps={(eng.stats['decode_steps'] - steps0) // 3}")
    assert streams["cb"] == streams["cb_step"], \
        "fused horizon decode must be bit-identical to single-step"
    row("serve_cb_vs_wave_speedup", results["cb"] / results["wave"],
        "continuous-batching tok/s over wave tok/s (>=1 expected)")
    fused_speedup = results["cb"] / results["cb_step"]
    disp_drop = (metrics["cb_step"]["dispatches_per_token"]
                 / max(metrics["cb"]["dispatches_per_token"], 1e-9))
    row("serve_fused_vs_single_step_speedup", fused_speedup,
        f"horizon-8 tok/s over one-dispatch-per-token (>=1.3 target), "
        f"dispatches/token drop {disp_drop:.1f}x (>=4 target), "
        "token streams bit-identical")
    state["serve_cb_speedup"] = results["cb"] / results["wave"]
    from repro.core.plan_search import PREDICTION_BAND
    state.setdefault("bench_json", {})["serve_cb"] = {
        "engines": metrics,
        "fused_vs_single_step_tok_s": round(fused_speedup, 3),
        "dispatches_per_token_drop": round(disp_drop, 2),
        "streams_bit_identical": True,
        # popped like _run_meta by the gate/diff; the band step reads it
        "_predicted": dict(predicted, band=list(PREDICTION_BAND)),
    }


def _measure_cb_engine(eng, stream, reps: int = 3):
    """Shared serving-engine measurement harness (serve_paged/serve_quant):
    one unmeasured compile pass, `reps` measured replays, median-by-wall
    pick.  Returns (median_pass, per-pass stream dicts, core metrics) where
    median_pass = (done, wall_s, tok_s, ttft_ms list)."""
    from repro.serving.stream import replay

    replay(eng, stream, warmup=False)  # compile pass
    disp0 = eng.stats["decode_dispatches"]
    steps0 = eng.stats["decode_steps"]
    lanes0 = eng.stats["active_lane_steps"]
    passes = [replay(eng, stream, warmup=False) for _ in range(reps)]
    median = sorted(passes, key=lambda p: p[1])[reps // 2]
    done, wall, tok_s, ttft = median
    toks = sum(len(r.tokens_out) for r in done)
    disp_tok = (eng.stats["decode_dispatches"] - disp0) / reps / toks
    conc = ((eng.stats["active_lane_steps"] - lanes0)
            / max(eng.stats["decode_steps"] - steps0, 1))
    metrics = {
        "tok_s": round(tok_s, 2),
        "ttft_p50_ms": round(float(np.percentile(ttft, 50)), 3),
        "ttft_p95_ms": round(float(np.percentile(ttft, 95)), 3),
        "dispatches_per_token": round(disp_tok, 4),
        "sustained_concurrency": round(conc, 2),
    }
    streams = [{r.rid: tuple(r.tokens_out) for r in p[0]} for p in passes]
    return median, streams, metrics


def serve_paged(state: Dict) -> None:
    """The `--shared-prefix` workload: paged KV + radix prefix reuse vs the
    dense-slot engine at *equal KV HBM* on a shared-system-prompt stream
    (`serving/stream.shared_prefix_requests`).

    The dense engine reserves a worst-case slot row per lane, so a fixed KV
    budget caps it at `dense_batch` lanes; the paged engine spends the same
    bytes as a page pool, where prefix sharing + actual-length allocation
    fit ~2x the lanes, and prefix-hit admissions skip prefill entirely.
    Streams must be bit-identical (one pinned impl, and the forced-token
    suffix ingest writes exactly the KV a cold prefill would)."""
    import jax as _jax
    from repro.configs import get_config
    from repro.kernels import ops as kops
    from repro.models.transformer import init_params, make_model
    from repro.serving.engine import ContinuousBatchingEngine
    from repro.serving.stream import shared_prefix_requests

    cfg = get_config("smollm-135m").reduced()
    model = make_model(cfg, remat=False)
    params = init_params(cfg, _jax.random.PRNGKey(0))
    stream = shared_prefix_requests(np.random.default_rng(0), 24,
                                    cfg.vocab_size, prefix_len=48,
                                    suffix_range=(3, 9), budgets=(16, 48),
                                    rate=300.0)
    page_size = 16
    dense_batch = 4
    buckets, max_decode = (64,), 96
    kv_rows = dense_batch * (max(buckets) + max_decode)  # dense KV budget
    setups = (
        ("dense_slots", dict(paged=False, max_batch=dense_batch)),
        # same KV bytes, spent as a shared page pool: 2x the lanes
        ("paged", dict(max_batch=2 * dense_batch, page_size=page_size,
                       num_pages=kv_rows // page_size + 1)),
    )
    metrics, streams = {}, {}
    with kops.pinned_impl("ref"):
        for name, kw in setups:
            eng = ContinuousBatchingEngine(
                model, params, buckets=buckets, max_decode_len=max_decode,
                **kw)
            (done, wall, tok_s, ttft), streams[name], metrics[name] = \
                _measure_cb_engine(eng, stream)
            toks = sum(len(r.tokens_out) for r in done)
            metrics[name]["max_batch"] = eng.max_batch
            if eng.paged:
                metrics[name].update(
                    prefix_hits=eng.stats["prefix_hits"],
                    prefix_hit_tokens=eng.stats["prefix_hit_tokens"],
                    prefills=eng.stats["prefills"],
                    pages_peak=eng.stats["pages_peak"],
                    preemptions=eng.stats["preemptions"])
            row(f"serve_paged_{name}_per_token", wall / toks * 1e6,
                f"{tok_s:.1f}tok/s "
                f"conc={metrics[name]['sustained_concurrency']:.2f} "
                f"ttft_p50={np.percentile(ttft, 50):.1f}ms "
                f"disp/tok={metrics[name]['dispatches_per_token']:.3f}")
    for k in range(3):  # every pass: cold tree on 1, warm prefix cache after
        assert streams["dense_slots"][k] == streams["paged"][k], \
            f"paged stream diverged from dense slots on pass {k}"
    speedup = metrics["paged"]["tok_s"] / metrics["dense_slots"]["tok_s"]
    conc_gain = (metrics["paged"]["sustained_concurrency"]
                 / max(metrics["dense_slots"]["sustained_concurrency"], 1e-9))
    row("serve_paged_vs_dense_tok_s", speedup,
        "paged tok/s over dense slots at equal KV HBM (>=1.3 target)")
    row("serve_paged_vs_dense_concurrency", conc_gain,
        "sustained concurrent requests, paged/dense (>=1.5 target)")
    state.setdefault("bench_json", {})["serve_paged"] = {
        "engines": metrics,
        "paged_vs_dense_tok_s": round(speedup, 3),
        "paged_vs_dense_concurrency": round(conc_gain, 3),
        "streams_bit_identical": True,
    }


def serve_quant(state: Dict) -> None:
    """The `--kv-dtype int8` axis: quantized KV-cache pages vs the bf16
    paged engine at *equal KV HBM*.

    int8 pages store ~(hd+4)/(2*hd) of the bf16 bytes per cache row
    (values at 1 B + one f32 scale per row per kv head), so the same byte
    budget buys ~1.6-2x the pages — sized here via
    `serving.engine.kv_page_bytes` — and the int8 engine sustains more
    resident lanes on a pool-bound stream.  Accuracy is measured as the
    greedy token-match rate against the bf16 engine's streams
    (>=0.99 gated): the model is first fitted to the affine-cycle task
    (models/synthetic.py) because stream agreement is only a meaningful
    instrument on a model with a confident predictive distribution —
    random-init top-2 logit gaps cluster at zero and *any* numeric
    difference, including bf16 summation order, flips tokens.
    """
    from repro.configs import get_config
    from repro.kernels import ops as kops
    from repro.models.synthetic import affine_prompts, fit_affine_lm
    from repro.models.transformer import make_model
    from repro.serving.engine import (
        ContinuousBatchingEngine, Request, kv_page_bytes,
    )

    cfg = get_config("smollm-135m").reduced()
    model = make_model(cfg, remat=False)
    params = fit_affine_lm(model)  # cached per process; ~1k adam steps
    rng = np.random.default_rng(0)
    prompts = affine_prompts(rng, 24, cfg.vocab_size, len_range=(6, 20))
    buds = rng.integers(16, 48, len(prompts))
    gaps = rng.exponential(1.0 / 300.0, len(prompts))
    arrivals = np.cumsum(gaps)
    stream = [Request(rid=i, prompt=p, max_new_tokens=int(buds[i]),
                      t_arrival=float(arrivals[i]))
              for i, p in enumerate(prompts)]

    page_size, bf16_pages = 16, 16  # pool-bound at ~4 resident lanes
    budget_bytes = bf16_pages * kv_page_bytes(cfg, page_size, "bf16")
    setups = []
    for name in ("bf16", "int8"):
        n_pages = budget_bytes // kv_page_bytes(cfg, page_size, name)
        setups.append((name, dict(kv_dtype=name, page_size=page_size,
                                  num_pages=int(n_pages) + 1)))
    metrics, streams = {}, {}
    with kops.pinned_impl("ref"):
        for name, kw in setups:
            eng = ContinuousBatchingEngine(
                model, params, max_batch=8, buckets=(32,),
                max_decode_len=64, **kw)
            (done, wall, tok_s, ttft), passes, metrics[name] = \
                _measure_cb_engine(eng, stream)
            streams[name] = passes[-1]  # greedy: identical across passes
            toks = sum(len(r.tokens_out) for r in done)
            metrics[name].update(
                num_pages=eng.pool.num_pages,
                pages_peak=eng.stats["pages_peak"],
                preemptions=eng.stats["preemptions"])
            row(f"serve_quant_{name}_per_token", wall / toks * 1e6,
                f"{tok_s:.1f}tok/s "
                f"conc={metrics[name]['sustained_concurrency']:.2f} "
                f"pages={eng.pool.num_pages} "
                f"ttft_p50={np.percentile(ttft, 50):.1f}ms")
    tot = sum(len(v) for v in streams["bf16"].values())
    matched = sum(
        sum(a == b for a, b in zip(streams["bf16"][rid], streams["int8"][rid]))
        for rid in streams["bf16"])
    match_rate = matched / max(tot, 1)
    speedup = metrics["int8"]["tok_s"] / metrics["bf16"]["tok_s"]
    conc_gain = (metrics["int8"]["sustained_concurrency"]
                 / max(metrics["bf16"]["sustained_concurrency"], 1e-9))
    row("serve_quant_int8_vs_bf16_tok_s", speedup,
        "int8 tok/s over bf16 paged at equal KV HBM (>=1.2 target)")
    row("serve_quant_int8_vs_bf16_concurrency", conc_gain,
        "sustained concurrent requests, int8/bf16 (>=1.5 target)")
    row("serve_quant_token_match_rate", match_rate,
        f"{matched}/{tot} greedy tokens identical to bf16 (>=0.99 gated)")
    state.setdefault("bench_json", {})["serve_quant"] = {
        "engines": metrics,
        "int8_vs_bf16_tok_s": round(speedup, 3),
        "int8_vs_bf16_concurrency": round(conc_gain, 3),
        "token_match_rate": round(match_rate, 4),
        "equal_kv_hbm_bytes": int(budget_bytes),
    }


def serve_sharded(state: Dict) -> None:
    """The tentpole's measurement: a `mode="serve"` plan on the forced
    multi-device host mesh (CI: XLA_FLAGS=--xla_force_host_platform_
    device_count=8) sharding the paged arena's kv-head dim over `model`,
    vs the single-device paged engine on the same shared-prefix stream.

    On fake host-platform devices the sharded path is *slower* (8 CPU
    "devices" share one socket and every gather is a real copy), so the
    gated ratio `sharded_vs_single_tok_s` is an overhead floor, not a
    speedup claim — the quantity that transfers is `token_match_rate`,
    gated at the absolute floor: sharded serving must be BIT-IDENTICAL
    to single-device (the serve plan's gather-form TP + shard_map'd
    paged decode make every cross-device reduction exact).
    """
    import dataclasses

    import jax as _jax
    from repro.configs import get_config
    from repro.core.cluster_builder import build_plan
    from repro.kernels import ops as kops
    from repro.launch.mesh import make_mesh
    from repro.models.transformer import init_params, make_model
    from repro.serving.engine import ContinuousBatchingEngine
    from repro.serving.stream import shared_prefix_requests

    n_dev = _jax.device_count()
    if n_dev < 2:
        row("serve_sharded_skipped", 0.0,
            "needs a multi-device host platform (set XLA_FLAGS="
            "--xla_force_host_platform_device_count=8 before jax init); "
            "gated keys omitted from this run")
        # drop the bench from the gate scope too: a single-device sweep
        # with --check-against must not fail on the baseline's
        # serve_sharded section it declared itself unable to measure
        state.setdefault("skipped", set()).add("serve_sharded")
        return
    cfg = dataclasses.replace(get_config("smollm-135m").reduced(),
                              n_heads=8, n_kv_heads=8)
    model = make_model(cfg, remat=False)
    params = init_params(cfg, _jax.random.PRNGKey(0))
    stream = shared_prefix_requests(np.random.default_rng(0), 16,
                                    cfg.vocab_size, prefix_len=48,
                                    suffix_range=(3, 9), budgets=(8, 24),
                                    rate=300.0)
    mesh = make_mesh((1, n_dev), ("data", "model"))
    state.setdefault("meshes", {})["serve_sharded"] = dict(mesh.shape)
    setups = (("single", None),
              ("sharded", build_plan(cfg, mesh, mode="serve")))
    metrics, streams, predicted = {}, {}, {}
    with kops.pinned_impl("ref"):
        for name, plan in setups:
            eng = ContinuousBatchingEngine(
                model, params, max_batch=4, buckets=(64,),
                max_decode_len=32, plan=plan)
            (done, wall, tok_s, ttft), streams[name], metrics[name] = \
                _measure_cb_engine(eng, stream)
            predicted[name] = _predicted_entry(
                _calibrate_engine(eng), eng, done, tok_s)
            toks = sum(len(r.tokens_out) for r in done)
            metrics[name].update(prefix_hits=eng.stats["prefix_hits"])
            row(f"serve_sharded_{name}_per_token", wall / toks * 1e6,
                f"{tok_s:.1f}tok/s devices={n_dev if plan else 1} "
                f"ttft_p50={np.percentile(ttft, 50):.1f}ms "
                f"hits={eng.stats['prefix_hits']}")
    tot = matched = 0
    for k in range(len(streams["single"])):  # every measured pass
        for rid, ts in streams["single"][k].items():
            tot += len(ts)
            matched += sum(a == b
                           for a, b in zip(ts, streams["sharded"][k][rid]))
    match_rate = matched / max(tot, 1)
    ratio = metrics["sharded"]["tok_s"] / metrics["single"]["tok_s"]
    row("serve_sharded_vs_single_tok_s", ratio,
        f"{n_dev}-way host-platform mesh overhead floor (fake devices "
        "share one socket; the ratio is gated so the sharded path can't "
        "silently rot)")
    row("serve_sharded_token_match_rate", match_rate,
        f"{matched}/{tot} tokens identical to single-device "
        "(bit-identity gated at the 0.99 absolute floor; expected 1.0)")
    from repro.core.plan_search import PREDICTION_BAND
    state.setdefault("bench_json", {})["serve_sharded"] = {
        "engines": metrics,
        "devices": n_dev,
        "sharded_vs_single_tok_s": round(ratio, 3),
        "token_match_rate": round(match_rate, 4),
        "_predicted": dict(predicted, band=list(PREDICTION_BAND)),
    }


def serve_throughput(state: Dict) -> None:
    """The throughput-mode tentpole: an exact=False ``serve_pipeline``
    plan (request-skewed schedule over stage-local paged arenas) vs the
    exact drained pipeline on the same stream, both on the forced
    multi-device host mesh (CI: XLA_FLAGS=--xla_force_host_platform_
    device_count=8).

    The exact schedule drains 2S-1 ticks per decode step (S lane
    microbatches + S-1 bubble); the skewed schedule keeps every stage on
    a different lane group's decode step and amortizes to S ticks per
    step — an asymptotic (2S-1)/S upper bound (1.875x at S=8), realized
    at 1.2-1.4x after drain ramp + paged-arena overhead (baseline-banded
    like every ratio).  Unlike every other
    serving bench this one is NOT bit-exact by contract: the skewed
    schedule reorders admissions across lane groups, so streams are
    gated by a token-match band (>=0.98, docs/serving.md §exactness
    contract) instead of equality — with the pinned ref kernels the
    observed rate is still 1.0 on this stream.
    """
    import dataclasses

    import jax as _jax
    from repro.configs import get_config
    from repro.core.cluster_builder import build_plan
    from repro.kernels import ops as kops
    from repro.launch.mesh import make_mesh
    from repro.models.transformer import init_params, make_model
    from repro.serving.engine import ContinuousBatchingEngine
    from repro.serving.stream import poisson_requests

    n_dev = _jax.device_count()
    if n_dev < 2:
        row("serve_throughput_skipped", 0.0,
            "needs a multi-device host platform (set XLA_FLAGS="
            "--xla_force_host_platform_device_count=8 before jax init); "
            "gated keys omitted from this run")
        state.setdefault("skipped", set()).add("serve_throughput")
        return
    n_stages = n_dev
    # four layer periods per stage: with a single tiny layer per stage
    # the per-tick dispatch overhead (collectives are real host copies)
    # swamps the schedule, and the bench would measure XLA fixed costs
    # rather than the drain bubble the skew schedule removes
    cfg = dataclasses.replace(get_config("smollm-135m").reduced(),
                              n_layers=4 * n_stages)
    model = make_model(cfg, remat=False)
    params = init_params(cfg, _jax.random.PRNGKey(0))
    mesh = make_mesh((n_stages,), ("stage",))
    state.setdefault("meshes", {})["serve_throughput"] = dict(mesh.shape)
    # decode-bound budgets: the skewed schedule's win is steady-state
    # ticks-per-step, so deep decodes amortize its S-1 drain ramp
    stream = poisson_requests(np.random.default_rng(0), 16, cfg.vocab_size,
                              len_range=(4, 14), budgets=(16, 33),
                              rate=300.0)
    setups = (
        ("exact", build_plan(cfg, mesh, mode="serve_pipeline", exact=True),
         dict(paged=False)),
        ("skewed", build_plan(cfg, mesh, mode="serve_pipeline", exact=False),
         dict(page_size=8)),
    )
    metrics, streams = {}, {}
    with kops.pinned_impl("ref"):
        for name, plan, kw in setups:
            eng = ContinuousBatchingEngine(
                model, params, max_batch=n_stages, buckets=(16,),
                max_decode_len=40, plan=plan, **kw)
            (done, wall, tok_s, ttft), streams[name], metrics[name] = \
                _measure_cb_engine(eng, stream)
            toks = sum(len(r.tokens_out) for r in done)
            metrics[name]["paged"] = eng.paged
            row(f"serve_throughput_{name}_per_token", wall / toks * 1e6,
                f"{tok_s:.1f}tok/s stages={n_stages} paged={eng.paged} "
                f"ttft_p50={np.percentile(ttft, 50):.1f}ms "
                f"disp/tok={metrics[name]['dispatches_per_token']:.3f}")
    tot = matched = 0
    for k in range(len(streams["exact"])):  # every measured pass
        for rid, ts in streams["exact"][k].items():
            tot += len(ts)
            matched += sum(a == b
                           for a, b in zip(ts, streams["skewed"][k][rid]))
    match_rate = matched / max(tot, 1)
    ratio = metrics["skewed"]["tok_s"] / metrics["exact"]["tok_s"]
    row("serve_throughput_vs_exact_tok_s", ratio,
        f"request-skewed tok/s over the exact drained pipeline on the "
        f"{n_stages}-stage host mesh (>=1.2 target; ceiling "
        f"{(2 * n_stages - 1) / n_stages:.2f}x)")
    row("serve_throughput_token_match_rate", match_rate,
        f"{matched}/{tot} tokens identical to the exact pipeline "
        "(band floor 0.98 — NOT an equality gate; docs/serving.md "
        "§exactness contract)")
    state.setdefault("bench_json", {})["serve_throughput"] = {
        "engines": metrics,
        "devices": n_dev,
        "stages": n_stages,
        "throughput_vs_exact_tok_s": round(ratio, 3),
        "token_match_rate": round(match_rate, 4),
    }


def serve_spec(state: Dict) -> None:
    """The `--draft-config` axis: greedy speculative decoding vs the plain
    fused-scan paged engine on a decode-bound stream.

    A 1-layer draft proposes up to `spec_k` tokens per lane inside one
    dispatch; the target verifies all k+1 positions in a single batched
    pass (contiguous-query paged attention) and the accepted prefix lands
    through the forced-token queue, so a dispatch can emit up to k+1
    tokens for ~one target forward.  Draft/target agreement is the whole
    game, so both are *fitted* affine-cycle LMs (models/synthetic.py):
    the high-agreement draft trains on the same corpus as the target, the
    mid-agreement draft trains on a corpus deviated at every value ≡ 0
    (mod 3) (`fit_affine_lm(..., disagree_every=3)`), dialing acceptance
    down and exercising the per-lane depth ladder.  Verification is
    lossless for greedy decoding — every emitted token is the target's
    own argmax — so `token_match_rate` is gated at the absolute floor and
    expected to be exactly 1.0 for BOTH drafts.
    """
    from repro.configs import get_config
    from repro.kernels import ops as kops
    from repro.models.synthetic import affine_prompts, fit_affine_lm
    from repro.models.transformer import make_model
    from repro.serving.engine import ContinuousBatchingEngine, Request

    # 8 layers: deep enough that the 1-layer draft is genuinely cheap
    # relative to the target (on the 2-layer reduced stack a draft step
    # costs nearly a target step and speculation cannot win anywhere);
    # the cb baseline below serves the *same* target, so the gated ratio
    # compares engines, not model sizes
    cfg = dataclasses.replace(get_config("smollm-135m").reduced(),
                              name="smollm-135m-spec-target", n_layers=8)
    model = make_model(cfg, remat=False)
    # 3k steps: the 8-layer stack underfits at the 1k default and its
    # noisier stream drags draft agreement (and so acceptance) down
    params = fit_affine_lm(model, steps=3000)
    dcfg = dataclasses.replace(cfg, name=cfg.name + "-draft", n_layers=1)
    draft = make_model(dcfg, remat=False)
    dparams_hi = fit_affine_lm(draft)  # same corpus -> high agreement
    dparams_mid = fit_affine_lm(draft, disagree_every=3)

    rng = np.random.default_rng(0)
    # decode-bound: short prompts, deep budgets — the regime where drafted
    # tokens amortize target forwards instead of prefill dominating
    prompts = affine_prompts(rng, 12, cfg.vocab_size, len_range=(6, 20))
    buds = rng.integers(24, 48, len(prompts))
    arrivals = np.cumsum(rng.exponential(1.0 / 300.0, len(prompts)))
    stream = [Request(rid=i, prompt=p, max_new_tokens=int(buds[i]),
                      t_arrival=float(arrivals[i]))
              for i, p in enumerate(prompts)]

    base_kw = dict(max_batch=4, buckets=(32,), max_decode_len=96,
                   page_size=16)
    setups = (
        ("cb", {}),
        ("spec", dict(spec_config=dict(
            draft_model=draft, draft_params=dparams_hi, spec_k=8))),
        ("spec_disagree", dict(spec_config=dict(
            draft_model=draft, draft_params=dparams_mid, spec_k=8))),
    )
    metrics, streams = {}, {}
    with kops.pinned_impl("ref"):
        for name, kw in setups:
            eng = ContinuousBatchingEngine(model, params, **base_kw, **kw)
            (done, wall, tok_s, ttft), streams[name], metrics[name] = \
                _measure_cb_engine(eng, stream)
            toks = sum(len(r.tokens_out) for r in done)
            extra = ""
            if eng.spec:
                acc = (eng.stats["spec_accepted"]
                       / max(eng.stats["spec_proposed"], 1))
                metrics[name].update(
                    acceptance=round(acc, 3),
                    spec_dispatches=eng.stats["spec_dispatches"],
                    draft_prefills=eng.stats["spec_draft_prefills"],
                    catchup_tokens=eng.stats["spec_catchup_tokens"])
                extra = f" accept={acc:.2f}"
            row(f"serve_spec_{name}_per_token", wall / toks * 1e6,
                f"{tok_s:.1f}tok/s "
                f"disp/tok={metrics[name]['dispatches_per_token']:.3f} "
                f"ttft_p50={np.percentile(ttft, 50):.1f}ms" + extra)
    # losslessness: every spec stream (any draft, any acceptance) must be
    # bit-identical to the plain engine's greedy streams, every pass
    tot = matched = 0
    for name in ("spec", "spec_disagree"):
        for k in range(len(streams["cb"])):
            for rid, ts in streams["cb"][k].items():
                tot += len(ts)
                matched += sum(a == b
                               for a, b in zip(ts, streams[name][k][rid]))
    match_rate = matched / max(tot, 1)
    speedup = metrics["spec"]["tok_s"] / metrics["cb"]["tok_s"]
    row("serve_spec_vs_cb_tok_s", speedup,
        "speculative tok/s over plain fused-scan cb at high draft "
        "agreement (>=1.3 target)")
    row("serve_spec_token_match_rate", match_rate,
        f"{matched}/{tot} greedy tokens identical to the plain engine "
        "(lossless verification; gated floor 0.99, expected exactly 1.0)")
    state.setdefault("bench_json", {})["serve_spec"] = {
        "engines": metrics,
        "spec_vs_cb_tok_s": round(speedup, 3),
        "token_match_rate": round(match_rate, 4),
    }


def serve_fleet(state: Dict) -> None:
    """Fleet routing policy comparison (docs/fleet.md): N independent
    engine replicas behind the FleetRouter serving a multi-tenant
    shared-system-prompt Poisson stream, affinity dispatch vs the
    round-robin control arm.

    Affinity routes every request of one prefix group to the replica
    whose radix tree holds that prefix, so the fleet pays ONE cold
    prefill per prefix; round-robin spreads each group over all replicas
    and pays up to one cold prefill per (replica, prefix) pair.  The
    gated quantities are the affinity/round-robin ratios of aggregate
    prefix_hit_tokens and tok/s — placement quality, not parallel
    speedup: in-process replicas drain sequentially on the host, so the
    wall-clock difference is exactly the skipped prefill work.

    Every measured pass uses FRESH prefixes (same shape, new tokens):
    replicas keep their radix trees between passes, and replaying one
    stream would let round-robin's second pass hit prefixes its first
    pass seeded on every replica, converging the two policies.

    Per-request token streams must be identical to a single plain engine
    serving the same stream (the fleet only chooses *where* a request
    runs), so token_match_rate is gated at the bit-identity floor and
    expected exactly 1.0."""
    import jax as _jax
    from repro.configs import get_config
    from repro.models.transformer import init_params, make_model
    from repro.serving.engine import ContinuousBatchingEngine
    from repro.serving.router import FleetConfig, build_fleet
    from repro.serving.stream import multi_prefix_requests, replay

    cfg = get_config("smollm-135m").reduced()
    model = make_model(cfg, remat=False)
    params = init_params(cfg, _jax.random.PRNGKey(0))
    n_replicas, n_prefixes, n_req, reps = 3, 8, 32, 3

    rng = np.random.default_rng(0)

    def mk_stream():
        # heavy system prompts (bucket-256 prefill) over short chat turns:
        # the regime where the cold-prefill bill the router routes around
        # dominates — a prefix hit saves a 256-token prefill and pays only
        # a handful of forced-token suffix steps
        return multi_prefix_requests(rng, n_req, cfg.vocab_size,
                                     n_prefixes=n_prefixes, prefix_len=240,
                                     suffix_range=(2, 6), budgets=(3, 7),
                                     rate=1500.0)

    # streams[0] warms compilation x2 (cold prefill + hit-admission
    # paths); streams[1] is a discarded fresh-prefix pass (warms the
    # admission-batch shapes each policy's steady state actually hits);
    # streams[2..reps+1] are the measured passes
    pass_streams = [mk_stream() for _ in range(reps + 2)]

    # pool sized so each pass's prefixes always fit (stale passes evict
    # first under LRU) — hit counts stay structural, not pressure-timing.
    # rebalance_margin is set high so the affinity arm measures pure
    # placement; the deadline-aware override is exercised in tests.
    engine_kw = dict(max_batch=4, buckets=(16, 32, 64, 256), num_pages=192)
    systems = (
        ("affinity", build_fleet(model, params, n_replicas,
                                 config=FleetConfig(route="affinity",
                                                    rebalance_margin=10_000),
                                 **engine_kw)),
        ("round_robin", build_fleet(model, params, n_replicas,
                                    config=FleetConfig(route="round-robin"),
                                    **engine_kw)),
        ("single", ContinuousBatchingEngine(model, params, **engine_kw)),
    )

    metrics, streams = {}, {}
    for name, sys_ in systems:
        is_fleet = hasattr(sys_, "replicas")

        def snap():
            st = sys_.stats() if is_fleet else sys_.stats
            return (st["prefix_hits"], st["prefix_hit_tokens"],
                    (sum(p["prefills"] for p in st["replicas"])
                     if is_fleet else st["prefills"]))
        replay(sys_, pass_streams[0], warmup=False)  # compile, cold paths
        replay(sys_, pass_streams[0], warmup=False)  # compile, hit paths
        replay(sys_, pass_streams[1], warmup=False)  # fresh-prefix warm
        hits0, hit_tok0, pre0 = snap()
        passes, per_pass = [], []
        for p in range(2, reps + 2):
            done, wall, tok_s, _ = replay(sys_, pass_streams[p],
                                          warmup=False)
            passes.append((done, wall, tok_s))
            per_pass.append({r.rid: tuple(r.tokens_out) for r in done})
        hits, hit_tok, prefills = (b - a for a, b in zip((hits0, hit_tok0,
                                                          pre0), snap()))
        done, wall, tok_s = sorted(passes, key=lambda p: p[1])[reps // 2]
        toks = sum(len(r.tokens_out) for r in done)
        streams[name] = per_pass
        metrics[name] = {
            "tok_s": round(tok_s, 2),
            "prefix_hits": int(hits),
            "prefix_hit_tokens": int(hit_tok),
            "prefills": int(prefills),
        }
        if is_fleet:
            metrics[name]["by_kind"] = dict(
                sorted(sys_.stats()["by_kind"].items()))
        row(f"serve_fleet_{name}_per_token", wall / toks * 1e6,
            f"{tok_s:.1f}tok/s hit_tokens={hit_tok} "
            f"prefills={prefills} over {reps} fresh-prefix passes")

    tot = matched = 0
    for p in range(reps):
        for arm in ("affinity", "round_robin"):
            for rid, ts in streams["single"][p].items():
                tot += len(ts)
                matched += sum(a == b
                               for a, b in zip(ts, streams[arm][p][rid]))
    match_rate = matched / max(tot, 1)
    hit_ratio = (metrics["affinity"]["prefix_hit_tokens"]
                 / max(metrics["round_robin"]["prefix_hit_tokens"], 1))
    tok_ratio = (metrics["affinity"]["tok_s"]
                 / metrics["round_robin"]["tok_s"])
    row("serve_fleet_affinity_vs_rr_hit_tokens", hit_ratio,
        f"{n_replicas} replicas, {n_prefixes} prefix groups: affinity "
        "prefix_hit_tokens over round-robin (>1 expected — one cold "
        "prefill per prefix vs per replica x prefix)")
    row("serve_fleet_affinity_vs_rr_tok_s", tok_ratio,
        "affinity tok/s over round-robin on the same stream (>1 expected "
        "— the skipped cold prefills; sequential drain, docs/fleet.md)")
    row("serve_fleet_token_match_rate", match_rate,
        f"{matched}/{tot} fleet tokens identical to the single plain "
        "engine (placement-only routing; gated floor 0.99, expected "
        "exactly 1.0)")
    state.setdefault("bench_json", {})["serve_fleet"] = {
        "engines": metrics,
        "replicas": n_replicas,
        "prefix_groups": n_prefixes,
        "fleet_affinity_vs_rr_hit_tokens": round(hit_ratio, 3),
        "fleet_affinity_vs_rr_tok_s": round(tok_ratio, 3),
        "token_match_rate": round(match_rate, 4),
    }


def serve_disagg(state: Dict) -> None:
    """Disaggregated prefill/decode pools (docs/serving.md §disaggregated
    serving) vs the colocated paged engine on the bursty phase-skewed
    stream (docs/perf.md §TTFT under burst): steady short-prompt decode
    traffic with synchronized long-prompt bursts, the ingress shape where
    colocated admission stalls every queued short request behind the
    burst's large-bucket prefills.

    In-process pools drain sequentially on the host, so the gated
    quantities are scheduling/shipping quality, not parallel speedup:

    - ``burst_ttft_p95_improvement``: colocated/disagg ratio of
      short-prompt TTFT p95 (>1 expected — ingest-first admission plus
      shortest-bucket-first cold ordering stop bursts from starving
      shorts);
    - ``hit_ttft_p95_improvement``: same ratio on an all-hits replay of a
      seen stream (decode-side TTFT must not regress when the radix tree
      already spans the prompt);
    - ``disagg_vs_colocated_tok_s``: shipping-overhead floor (<1 on one
      host — every cold admission pays an extra gather/ship/scatter
      dispatch triple), gated so the page-shipping path can't silently
      rot;
    - ``token_match_rate``: disagg must be BIT-IDENTICAL to colocated
      (prefill_admit writes what admit_cold would write and shipping is
      value-preserving), gated at the absolute 0.99 floor, expected 1.0.

    The hit-phase replay additionally hard-asserts the radix-spanning
    contract: prefix hits climb while ship_dispatches stays flat —
    a decode-side hit performs ZERO page transfers.

    Measured passes use FRESH streams (the radix tree persists between
    passes; replaying one stream would turn every cold admission into a
    hit and null the shipping path under test)."""
    import jax as _jax
    from repro.configs import get_config
    from repro.kernels import ops as kops
    from repro.models.transformer import init_params, make_model
    from repro.serving.engine import ContinuousBatchingEngine
    from repro.serving.stream import bursty_requests, replay

    n_dev = _jax.device_count()
    if n_dev < 2:
        row("serve_disagg_skipped", 0.0,
            "needs a multi-device host platform (set XLA_FLAGS="
            "--xla_force_host_platform_device_count=8 before jax init); "
            "gated keys omitted from this run")
        state.setdefault("skipped", set()).add("serve_disagg")
        return
    cfg = get_config("smollm-135m").reduced()
    model = make_model(cfg, remat=False)
    params = init_params(cfg, _jax.random.PRNGKey(0))
    n_req, reps = 24, 3
    # 1:1 pools: P() over a wider pool replicates params and arena across
    # its fake host devices (every op runs on all of them, every ship
    # copies to all of them), which measures replication overhead instead
    # of the handoff.  Wider splits are the cost model's job
    # (plan_search's disagg axis); the bench measures the mechanism.
    p_pool = d_pool = 1
    rng = np.random.default_rng(0)
    long_cut = 180  # bursty_requests long_range floor; short/long ranges
    # never overlap, so burst membership is classifiable from len(prompt)

    def mk_stream():
        # short_range floor >= page_size so every prompt caches at least
        # one full page — the hit-phase replay must be hits, not colds
        return bursty_requests(rng, n_req, cfg.vocab_size,
                               short_range=(24, 40), burst_every=6,
                               burst_size=3, budgets=(4, 8), rate=600.0)

    # streams[0] warms compilation x2 (cold + hit admission paths);
    # streams[1] is a discarded fresh pass (steady-state admission batch
    # shapes); streams[2..reps+1] are the measured passes; streams[-1]
    # drives the hit phase (served cold once, then replayed — all hits)
    pass_streams = [mk_stream() for _ in range(reps + 3)]
    # deadline_s widened past ref-impl CPU prefill timescales for BOTH
    # arms: at the production default every queued request is overdue
    # within one CPU prefill and both schedulers collapse to the
    # overdue-FIFO guarantee — the quantity under test is the admission
    # *ordering* split (ingest-first + SJF chunk vs FIFO), not the
    # shared overdue fallback
    engine_kw = dict(max_batch=4, buckets=(64, 256), max_decode_len=16,
                     num_pages=256, deadline_s=60.0)
    setups = (("colocated", {}), ("disagg", {"disagg": (p_pool, d_pool)}))
    names = [n for n, _ in setups]
    metrics, streams, streams_hit = {}, {}, {}
    pass_tok = {n: [] for n in names}    # per-pass tok/s
    pass_short = {n: [] for n in names}  # per-pass short-TTFT p95 (ms)
    pass_hit = {n: [] for n in names}    # per-replay hit-TTFT p95 (ms)
    pass_wall = {n: [] for n in names}   # (wall_s, tokens) per pass
    with kops.pinned_impl("ref"):
        engines = {}
        for name, extra in setups:
            eng = ContinuousBatchingEngine(model, params, **engine_kw,
                                           **extra)
            replay(eng, pass_streams[0], warmup=False)  # compile, cold
            replay(eng, pass_streams[0], warmup=False)  # compile, hits
            replay(eng, pass_streams[1], warmup=False)  # fresh warm
            engines[name] = eng
            streams[name] = []
        pre0 = {n: e.stats["prefills"] for n, e in engines.items()}
        ship0 = {n: e.stats.get("ship_dispatches", 0)
                 for n, e in engines.items()}
        # measured passes run ARM-PAIRED on the same fresh stream: the
        # bench box is small and shared, so wall-clock drift between
        # passes dwarfs the arm difference — pairing the arms inside one
        # load window and gating the MEDIAN of per-pass ratios is what
        # makes the ratios reproducible
        for p in range(2, reps + 2):
            for name, eng in engines.items():
                done, wall, tok_s, ttft = replay(eng, pass_streams[p],
                                                 warmup=False)
                streams[name].append(
                    {r.rid: tuple(r.tokens_out) for r in done})
                sh = [t for r, t in zip(done, ttft)
                      if len(r.prompt) < long_cut]
                pass_tok[name].append(tok_s)
                pass_short[name].append(float(np.percentile(sh, 95)))
                pass_wall[name].append(
                    (wall, sum(len(r.tokens_out) for r in done)))
        prefills = {n: e.stats["prefills"] - pre0[n]
                    for n, e in engines.items()}
        ships = {n: e.stats.get("ship_dispatches", 0) - ship0[n]
                 for n, e in engines.items()}
        # hit phase: seed one fresh stream cold in both arms, then paired
        # all-hit replays — every prompt is now spanned by the radix
        # tree, so the disagg arm must admit via the decode pool alone
        # (zero page transfers); hits skip prefill, so the p95 is
        # dispatch-cadence-dominated and needs the replay pooling too
        for name, eng in engines.items():
            replay(eng, pass_streams[-1], warmup=False)
        hits0 = {n: e.stats["prefix_hits"] for n, e in engines.items()}
        ship_h0 = {n: e.stats.get("ship_dispatches", 0)
                   for n, e in engines.items()}
        for _ in range(3):
            for name, eng in engines.items():
                done_h, _, _, t_h = replay(eng, pass_streams[-1],
                                           warmup=False)
                pass_hit[name].append(float(np.percentile(t_h, 95)))
                streams_hit[name] = {r.rid: tuple(r.tokens_out)
                                     for r in done_h}
        for name, eng in engines.items():
            d_hits = eng.stats["prefix_hits"] - hits0[name]
            d_ship = eng.stats.get("ship_dispatches", 0) - ship_h0[name]
            assert d_hits > 0, \
                f"serve_disagg[{name}]: hit-phase replay produced no " \
                f"prefix hits — the phase is not measuring hits"
            if name == "disagg":
                assert d_ship == 0, \
                    f"serve_disagg: {d_ship} page-shipping dispatches " \
                    f"during the all-hits phase — a decode-side prefix " \
                    f"hit must perform ZERO transfers (docs/serving.md)"
                metrics[name] = {
                    "hit_phase_ship_dispatches": int(d_ship),
                    "hit_phase_prefix_hits": int(d_hits),
                    "ship_dispatches": int(ships[name]),
                    "shipped_pages": int(eng.stats["shipped_pages"]),
                    "shipped_bytes": int(eng.stats["shipped_bytes"]),
                }
        for name in names:
            tok_s = float(np.median(pass_tok[name]))
            wall, toks = sorted(pass_wall[name])[reps // 2]
            metrics.setdefault(name, {}).update(
                tok_s=round(tok_s, 2),
                prefills=int(prefills[name]),
                short_ttft_p95_ms=round(np.median(pass_short[name]), 2),
                hit_ttft_p95_ms=round(np.median(pass_hit[name]), 2))
            row(f"serve_disagg_{name}_per_token", wall / toks * 1e6,
                f"{tok_s:.1f}tok/s short_ttft_p95="
                f"{np.median(pass_short[name]):.1f}ms "
                f"prefills={prefills[name]}"
                + (f" ships={ships[name]}" if name == "disagg" else ""))
    tot = matched = 0
    for p in range(reps):
        for rid, ts in streams["colocated"][p].items():
            tot += len(ts)
            matched += sum(a == b
                           for a, b in zip(ts, streams["disagg"][p][rid]))
    for rid, ts in streams_hit["colocated"].items():
        tot += len(ts)
        matched += sum(a == b
                       for a, b in zip(ts, streams_hit["disagg"][rid]))
    match_rate = matched / max(tot, 1)
    med = lambda pairs: float(np.median(pairs))  # noqa: E731
    tok_ratio = med([d / c for d, c in zip(pass_tok["disagg"],
                                           pass_tok["colocated"])])
    burst_ratio = med([c / max(d, 1e-9)
                       for c, d in zip(pass_short["colocated"],
                                       pass_short["disagg"])])
    hit_ratio = med([c / max(d, 1e-9)
                     for c, d in zip(pass_hit["colocated"],
                                     pass_hit["disagg"])])
    row("serve_disagg_vs_colocated_tok_s", tok_ratio,
        f"{p_pool}:{d_pool} pools on {n_dev} host devices: shipping "
        "overhead floor (<1 expected — every cold admission pays the "
        "gather/ship/scatter triple; gated so the path can't rot)")
    row("serve_disagg_burst_ttft_p95_improvement", burst_ratio,
        "colocated/disagg short-prompt TTFT p95 (>1 expected — "
        "ingest-first admission + shortest-bucket-first cold ordering "
        "keep bursts from starving shorts; docs/perf.md §TTFT under "
        "burst)")
    row("serve_disagg_hit_ttft_p95_improvement", hit_ratio,
        "colocated/disagg TTFT p95 on the all-hits replay (decode-side "
        "admission must not regress when the radix tree spans the prompt)")
    row("serve_disagg_token_match_rate", match_rate,
        f"{matched}/{tot} disagg tokens identical to colocated across "
        "measured + hit passes (bit-identity floor 0.99, expected 1.0)")
    state.setdefault("bench_json", {})["serve_disagg"] = {
        "engines": metrics,
        "devices": n_dev,
        "disagg": [p_pool, d_pool],
        "disagg_vs_colocated_tok_s": round(tok_ratio, 3),
        "burst_ttft_p95_improvement": round(burst_ratio, 3),
        "hit_ttft_p95_improvement": round(hit_ratio, 3),
        "token_match_rate": round(match_rate, 4),
    }


PLAN_FAMILIES = ("smollm-135m", "ibert-base", "phi3-medium-14b",
                 "moonshot-v1-16b-a3b")


def _plans_dir() -> str:
    import os
    return os.path.join(os.path.dirname(os.path.abspath(__file__)), "plans")


def _plan_snapshot_path(arch: str) -> str:
    import os
    return os.path.join(_plans_dir(), arch.replace("-", "_") + ".json")


def _default_profile():
    """The traffic profile the committed plan snapshots are searched for:
    benchmarks/profiles/default.json when present (the file CI's
    plan-search job and `serve.py --plan auto --traffic` share), else the
    built-in TrafficProfile defaults (kept identical)."""
    import os
    from repro.core.plan_search import TrafficProfile
    p = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "profiles", "default.json")
    return TrafficProfile.from_json(p) if os.path.exists(p) \
        else TrafficProfile()


def plan_search_bench(state: Dict) -> None:
    """Cost-model plan auto-search over the CI config families
    (docs/serving.md §plan auto-search): searches each family against the
    default traffic profile and emits the chosen-plan snapshots that
    `--check-plans` diffs against benchmarks/plans/ (the CI snapshot
    gate) and `--write-plans` refreshes.  The search itself is pure
    arithmetic on jaxpr-traced counts — deterministic, so any drift is a
    code/profile change, never noise."""
    from repro.configs import get_config
    from repro.core.plan_search import search, to_snapshot

    profile = _default_profile()
    archs = state.get("plan_archs") or PLAN_FAMILIES
    snaps = {}
    for arch in archs:
        cfg = get_config(arch)
        t0 = time.perf_counter()
        res = search(cfg, profile)
        elapsed = time.perf_counter() - t0
        ch = res.chosen
        derived = (f"chosen={ch.key} pred={ch.tok_s:.0f}tok/s "
                   f"ttft={ch.ttft_ms:.2f}ms hbm={ch.hbm_frac:.2f}"
                   if ch else "chosen=NONE")
        row(f"plan_search_{arch.replace('-', '_')}", elapsed * 1e6,
            f"{derived} {res.n_feasible}/{len(res.scores)} feasible "
            f"frontier={len(res.frontier)}")
        snaps[arch] = to_snapshot(cfg, res)
    state["plan_snapshots"] = snaps
    state.setdefault("bench_json", {})["plan_search"] = {
        "profile": profile.to_dict(),
        "snapshots": snaps,
    }


def check_plans(snaps: Dict) -> int:
    """Snapshot gate: diff freshly searched plans against the committed
    benchmarks/plans/ files.  Structural drift (chosen candidate,
    frontier, profile, cost-model version) or a missing snapshot fails;
    predicted-number deltas are informational (plan_search.diff_snapshots
    owns the split).  0 = clean, 1 = drift."""
    import json
    import os
    from repro.core.plan_search import diff_snapshots
    failed = False
    for arch, snap in snaps.items():
        path = _plan_snapshot_path(arch)
        if not os.path.exists(path):
            print(f"PLAN SNAPSHOT MISSING {path} (family {arch})")
            failed = True
            continue
        with open(path) as f:
            committed = json.load(f)
        hard, info = diff_snapshots(committed, snap)
        for line in info:
            print(f"  plan {arch} (informational): {line}")
        if hard:
            print(f"PLAN SNAPSHOT DRIFT {arch} vs {path}:")
            for line in hard:
                print(f"  DRIFT {line}")
            failed = True
        else:
            print(f"plan snapshot OK: {arch}")
    if failed:
        print("plan snapshots drifted: if the new choices are intended, "
              "refresh with `python benchmarks/run.py plan_search "
              "--write-plans` (CI: the refresh-plans workflow_dispatch "
              "job) and commit benchmarks/plans/")
        return 1
    return 0


def write_plans(snaps: Dict) -> None:
    import json
    import os
    os.makedirs(_plans_dir(), exist_ok=True)
    for arch, snap in snaps.items():
        path = _plan_snapshot_path(arch)
        with open(path, "w") as f:
            json.dump(snap, f, indent=2, sort_keys=True)
            f.write("\n")
        print(f"wrote plan snapshot {path}")


def _calibrate_engine(eng, reps: int = 3, ns=(1, 8)):
    """Two-point decode calibration for the predicted-vs-measured gate
    (docs/perf.md §cost model): time the engine's fused n-step dispatch
    at n=1 and n=8 on probe caches (the serving caches are untouched),
    then split marginal step cost from fixed dispatch overhead — the
    measured analogue of the paper's Table 1 T/I fit."""
    from repro.core.plan_search import DeviceCalibration

    ex = eng.executor
    pargs = ((eng.page_size, eng.kv.num_pages, eng.max_pages,
              eng.kv_dtype) if eng.paged else ())
    st = ex.fresh_state(ex.init_caches(eng.paged, *pargs), eng.paged)
    t = {}
    for n in ns:
        np.asarray(ex.decode(st, n, eng.paged))  # compile
        ts = []
        for _ in range(reps):
            t0 = time.perf_counter()
            np.asarray(ex.decode(st, n, eng.paged))
            ts.append(time.perf_counter() - t0)
        t[n] = float(np.median(ts))
    # prefill probe: one batch-1 bucketed dispatch (the admission unit),
    # at the engine's largest bucket (conservative for shorter prompts)
    prompt = [[1] * (max(eng.buckets) - 1)]
    jax.block_until_ready(ex.prefill_prompts(prompt, 1, bucket_cache=True))
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(
            ex.prefill_prompts(prompt, 1, bucket_cache=True))
        ts.append(time.perf_counter() - t0)
    return DeviceCalibration.from_two_point(
        t[ns[0]], ns[0], t[ns[1]], ns[1], t_prefill=float(np.median(ts)))


def _predicted_entry(calib, eng, done, measured_tok_s: float) -> Dict:
    """One engine's `_predicted` stamp: model prediction from the
    calibrated costs + the stream's declared shape, next to measured."""
    from repro.core.plan_search import predict_engine_tok_s

    toks = sum(len(r.tokens_out) for r in done)
    ptoks = sum(len(r.prompt) for r in done)
    pred = predict_engine_tok_s(
        calib, n_requests=len(done), total_tokens=toks,
        prompt_tokens=ptoks, max_batch=eng.max_batch,
        horizon=eng.decode_horizon)
    return {
        "predicted_tok_s": round(pred, 2),
        "measured_tok_s": round(measured_tok_s, 2),
        "ratio": round(pred / max(measured_tok_s, 1e-9), 4),
        "t_step_ms": round(calib.t_step_s * 1e3, 4),
        "t_dispatch_ms": round(calib.t_dispatch_s * 1e3, 4),
        "t_prefill_ms": round(calib.t_prefill_s * 1e3, 4),
    }


BENCHES = {
    "table1": table1_encoder_latency,
    "table2": table2_full_model_eq1,
    "table3": table3_padding_vs_nopadding,
    "table4": table4_throughput,
    "sec9": sec9_v5e_estimate,
    "table5": table5_accelerator_comparison,
    "fig15": fig15_cluster_resources,
    "gmi": gmi_collective_models,
    "kernels": bench_int8_kernels,
    "serve_cb": serve_cb,
    "serve_paged": serve_paged,
    "serve_quant": serve_quant,
    "serve_sharded": serve_sharded,
    "serve_throughput": serve_throughput,
    "serve_spec": serve_spec,
    "serve_fleet": serve_fleet,
    "serve_disagg": serve_disagg,
    "plan_search": plan_search_bench,
}

# benches whose state is produced by earlier benches in the full sweep
_ORDER = ["table1", "table2", "table3", "table4", "sec9", "table5",
          "fig15", "gmi", "kernels", "serve_cb", "serve_paged",
          "serve_quant", "serve_sharded", "serve_throughput", "serve_spec",
          "serve_fleet", "serve_disagg", "plan_search"]

# every gated section DECLARES the gate-owned metrics it emits (the leaf
# names _gate_walk owns).  --list derives its table from these
# declarations — not from the committed baseline — so a new gated section
# shows up the moment it exists and a stale baseline is loudly flagged
# instead of silently shipping the section ungated.
serve_cb.gate_keys = ("tok_s", "dispatches_per_token",
                      "fused_vs_single_step_tok_s",
                      "dispatches_per_token_drop")
serve_paged.gate_keys = ("tok_s", "dispatches_per_token",
                         "paged_vs_dense_tok_s",
                         "paged_vs_dense_concurrency")
serve_quant.gate_keys = ("tok_s", "dispatches_per_token",
                         "int8_vs_bf16_tok_s", "int8_vs_bf16_concurrency",
                         "token_match_rate")
serve_sharded.gate_keys = ("tok_s", "dispatches_per_token",
                           "sharded_vs_single_tok_s", "token_match_rate")
serve_throughput.gate_keys = ("tok_s", "dispatches_per_token",
                              "throughput_vs_exact_tok_s",
                              "token_match_rate")
serve_spec.gate_keys = ("tok_s", "dispatches_per_token",
                        "spec_vs_cb_tok_s", "token_match_rate")
serve_fleet.gate_keys = ("tok_s", "fleet_affinity_vs_rr_hit_tokens",
                         "fleet_affinity_vs_rr_tok_s", "token_match_rate")
serve_disagg.gate_keys = ("tok_s", "disagg_vs_colocated_tok_s",
                          "burst_ttft_p95_improvement",
                          "hit_ttft_p95_improvement", "token_match_rate")
_NEEDS = {"table2": ["table1"], "table3": ["table1"],
          "table4": ["table1", "table3"], "table5": ["sec9"]}

# perf-regression gate thresholds (--check-against): tok/s may regress up
# to 25% before failing (CI boxes are noisy); dispatches/token is
# scheduling-deterministic up to arrival-timing jitter, so it gets a
# tighter 10% band — any real fusion regression is far larger than that.
# Absolute tok/s is machine-relative (regenerate the baseline when the
# runner class changes); the speedup *ratios* below are gated too because
# they compare two engines measured on the same box in the same run and
# therefore transfer across hardware.
TOK_S_REGRESSION = 0.25
DISP_TOK_INCREASE = 0.10
RATIO_KEYS = ("paged_vs_dense_tok_s", "paged_vs_dense_concurrency",
              "fused_vs_single_step_tok_s", "dispatches_per_token_drop",
              "int8_vs_bf16_tok_s", "int8_vs_bf16_concurrency",
              "sharded_vs_single_tok_s", "throughput_vs_exact_tok_s",
              "spec_vs_cb_tok_s", "fleet_affinity_vs_rr_hit_tokens",
              "fleet_affinity_vs_rr_tok_s", "disagg_vs_colocated_tok_s",
              "burst_ttft_p95_improvement", "hit_ttft_p95_improvement")
# absolute floor: int8 greedy streams must match bf16 on >=99% of tokens —
# accuracy is not machine-relative, so no baseline-relative band applies
TOKEN_MATCH_FLOOR = 0.99
# per-section overrides: serve_throughput is explicitly NOT bit-exact
# (request-skewed schedule; docs/serving.md §exactness contract) and is
# gated at the contract's 0.98 band instead of the bit-identity floor
_MATCH_FLOORS = {"serve_throughput": 0.98}
_GATED_LEAVES = ("tok_s", "dispatches_per_token", "token_match_rate")


def _run_meta(state: Dict) -> Dict:
    """Provenance stamp for every BENCH_*.json: which jax, which devices,
    which meshes produced these numbers.  Absolute tok_s is meaningless
    without it — a baseline regenerated on a different runner class or
    device count LOOKS like a perf change otherwise.  Never gated: the
    gate and the perf.yml diff both pop `_run_meta` before comparing."""
    dev = jax.devices()[0]
    return {
        "jax_version": jax.__version__,
        "platform": dev.platform,
        "device_kind": dev.device_kind,
        "device_count": jax.device_count(),
        "mesh_shapes": state.get("meshes", {}),
    }


def _gate_walk(base, cur, path=""):
    """Compare a bench_json tree against a committed baseline; returns a
    list of violation strings (empty = gate passes).  Only the metrics the
    gate owns are compared — every `tok_s` (lower = regression), every
    `dispatches_per_token` (higher = regression), the speedup ratios and
    the absolute `token_match_rate` floor; other keys are context."""
    bad = []
    if isinstance(base, dict):
        for k, v in base.items():
            sub = cur.get(k) if isinstance(cur, dict) else None
            if sub is None and not isinstance(v, dict):
                if k in _GATED_LEAVES or k in RATIO_KEYS:
                    bad.append(f"{path}{k}: missing from current run")
                continue
            bad += _gate_walk(v, sub, f"{path}{k}.")
        return bad
    key = path.rstrip(".").rsplit(".", 1)[-1]
    if not isinstance(cur, (int, float)) or isinstance(cur, bool):
        if key in _GATED_LEAVES or key in RATIO_KEYS:
            bad.append(f"{path.rstrip('.')}: non-numeric value {cur!r} "
                       f"in current run (baseline {base})")
        return bad
    if key == "tok_s" or key in RATIO_KEYS:
        floor = base * (1 - TOK_S_REGRESSION)
        if cur < floor:
            bad.append(f"{path.rstrip('.')}: {cur} < {floor:.2f} "
                       f"(baseline {base}, -{TOK_S_REGRESSION:.0%} floor)")
    elif key == "dispatches_per_token":
        ceil = base * (1 + DISP_TOK_INCREASE)
        if cur > ceil:
            bad.append(f"{path.rstrip('.')}: {cur} > {ceil:.4f} "
                       f"(baseline {base}, +{DISP_TOK_INCREASE:.0%} ceiling)")
    elif key == "token_match_rate":
        floor = _MATCH_FLOORS.get(path.split(".", 1)[0], TOKEN_MATCH_FLOOR)
        if cur < floor:
            bad.append(f"{path.rstrip('.')}: {cur} < {floor} "
                       f"(absolute accuracy floor; baseline {base})")
    return bad


def _gated_paths(tree, path=""):
    """Dotted paths of every gate-owned metric in a bench_json tree."""
    out = []
    if isinstance(tree, dict):
        for k, v in tree.items():
            if isinstance(v, dict):
                out += _gated_paths(v, f"{path}{k}.")
            elif k in _GATED_LEAVES or k in RATIO_KEYS:
                out.append(f"{path}{k}")
    return out


def _pop_predicted(tree: Dict) -> Dict:
    """Strip the per-section `_predicted` stamps (popped like `_run_meta`
    before baseline comparison, so baselines committed before the
    prediction gate existed stay valid and never grow the key)."""
    return {k: ({kk: vv for kk, vv in v.items() if kk != "_predicted"}
                if isinstance(v, dict) else v)
            for k, v in tree.items()}


def check_prediction_band(bench_json: Dict) -> List[str]:
    """The predicted-vs-measured accuracy gate (docs/perf.md §cost
    model): every `_predicted` entry a serve bench stamped must have its
    predicted/measured tok/s ratio inside the band it declared.  Returns
    violation strings (empty = within band)."""
    bad = []
    for sec, body in sorted(bench_json.items()):
        if not isinstance(body, dict):
            continue
        pred = body.get("_predicted")
        if not isinstance(pred, dict):
            continue
        lo, hi = pred.get("band", (0.0, float("inf")))
        for name, entry in sorted(pred.items()):
            if not isinstance(entry, dict) or "ratio" not in entry:
                continue
            r = entry["ratio"]
            if not lo <= r <= hi:
                bad.append(
                    f"{sec}.{name}: predicted/measured tok/s ratio {r} "
                    f"outside [{lo}, {hi}] (predicted "
                    f"{entry.get('predicted_tok_s')}, measured "
                    f"{entry.get('measured_tok_s')}) — the cost model "
                    "has drifted from the device; recalibrate or fix "
                    "core/plan_search before trusting its plans")
    return bad


def check_against(baseline_path: str, bench_json: Dict,
                  ran=None) -> int:
    """Exit-code-style perf gate: 0 = within thresholds, 1 = regression.

    Fails with an explicit message — never a KeyError — when the baseline
    and the current run disagree on *which* gated metrics exist: a metric
    the baseline expects but the run didn't produce is a regression, and a
    gated metric the run produced but the baseline has never seen (e.g.
    the first run after adding a benchmark axis) means the committed
    baseline must be refreshed before the gate can vouch for it.

    `ran` (bench names this invocation executed) scopes the comparison to
    the baseline's matching top-level sections: the PR perf-smoke job runs
    the serving benches and the multi-device job runs `serve_sharded`
    against the SAME committed baseline — each gate vouches only for the
    sections its own run produced, while "the bench ran but a gated
    metric vanished" still fails inside a section.
    """
    import json
    with open(baseline_path) as f:
        base = json.load(f)
    base.pop("rows", None)
    base.pop("_meta", None)
    base.pop("_run_meta", None)
    base = _pop_predicted(base)
    # enforce the predicted-vs-measured band BEFORE stripping the stamps:
    # the band is self-declared per section, never baseline-relative
    pred_bad = check_prediction_band(bench_json)
    bench_json = _pop_predicted(
        {k: v for k, v in bench_json.items() if k != "_run_meta"})
    if ran is not None:
        base = {k: v for k, v in base.items() if k in ran}
        bench_json = {k: v for k, v in bench_json.items() if k in ran}
    # report EVERYTHING wrong in one run: all gated metrics the baseline
    # has never seen AND all threshold violations against the metrics it
    # does have — a first-run-after-new-section failure must not mask a
    # real regression in the established sections (and vice versa)
    missing = sorted(set(_gated_paths(bench_json)) - set(_gated_paths(base)))
    bad = _gate_walk(base, bench_json)
    if missing:
        print(f"PERF GATE UNUSABLE: {baseline_path} has no entry for "
              f"gated metric(s) produced by this run:")
        for m in missing:
            print(f"  MISSING BASELINE KEY {m}")
        print("refresh the committed baseline (CI: the baseline-refresh "
              "workflow_dispatch job; locally: `python benchmarks/run.py "
              "serve_cb serve_spec --shared-prefix --kv-dtype int8 "
              "--write-baseline benchmarks/baseline.json` on a quiet box) "
              "and commit it")
    if bad:
        print(f"PERF GATE FAILED vs {baseline_path}:")
        for b in bad:
            print(f"  REGRESSION {b}")
    if pred_bad:
        print("PREDICTION BAND FAILED:")
        for b in pred_bad:
            print(f"  PREDICTION {b}")
    if missing or bad or pred_bad:
        return 1
    print(f"perf gate OK vs {baseline_path}")
    return 0


def main(argv=None) -> None:
    import json
    import sys
    from repro.launch.compile_cache import enable_compile_cache
    args = list(argv if argv is not None else sys.argv[1:])
    enable_compile_cache()

    def _path_flag(flag):
        if flag not in args:
            return None
        i = args.index(flag)
        try:
            p = args[i + 1]
        except IndexError:
            raise SystemExit(f"{flag} requires a value")
        del args[i:i + 2]
        return p

    if "--list" in args:  # enumerate benches + their DECLARED gate keys
        # keys come from each section's own `gate_keys` declaration, not
        # from the committed baseline — a freshly added gated section is
        # listed (and flagged) even before the baseline has been
        # refreshed, so it can never silently ship ungated
        import os
        base = {}
        bp = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "baseline.json")
        if os.path.exists(bp):
            with open(bp) as f:
                base = json.load(f)
            base.pop("_meta", None)
            base.pop("rows", None)
        print(f"{'bench':<14} declared gate keys (baseline: {bp})")
        stale = []
        for name in _ORDER:
            keys = getattr(BENCHES[name], "gate_keys", ())
            if not keys:
                print(f"{name:<14} (not gated)")
                continue
            covered = set(p.rsplit(".", 1)[-1]
                          for p in _gated_paths(base.get(name, {})))
            missing = [k for k in keys if k not in covered]
            mark = (f"  [NOT IN BASELINE: {', '.join(missing)}]"
                    if missing else "")
            if missing:
                stale.append(name)
            print(f"{name:<14} " + ", ".join(keys) + mark)
        if stale:
            print(f"\nWARNING: baseline lacks gated keys for "
                  f"{', '.join(stale)} — refresh it before merging "
                  "(--write-baseline merges per-section)")
        # plan-snapshot staleness (the other committed trust artifact):
        # structural check only — missing file, cost-model version skew,
        # or a profile that no longer matches the default; full drift
        # needs the search itself (`plan_search --check-plans`)
        from repro.core.plan_search import COST_MODEL_VERSION
        profile = _default_profile().to_dict()
        print(f"\n{'plan family':<22} snapshot ({_plans_dir()})")
        plan_stale = []
        for arch in PLAN_FAMILIES:
            path = _plan_snapshot_path(arch)
            if not os.path.exists(path):
                status = "MISSING (run plan_search --write-plans)"
            else:
                with open(path) as f:
                    snap = json.load(f)
                if snap.get("cost_model_version") != COST_MODEL_VERSION:
                    status = (f"STALE: cost_model_version "
                              f"{snap.get('cost_model_version')} != "
                              f"{COST_MODEL_VERSION}")
                elif snap.get("profile") != profile:
                    status = "STALE: profile differs from default profile"
                else:
                    ch = (snap.get("chosen") or {}).get("key", "NONE")
                    status = f"ok  chosen={ch}"
            if not status.startswith("ok"):
                plan_stale.append(arch)
            print(f"{arch:<22} {status}")
        if plan_stale:
            print(f"\nWARNING: plan snapshot missing/stale for "
                  f"{', '.join(plan_stale)} — refresh with `python "
                  "benchmarks/run.py plan_search --write-plans` (choice "
                  "drift itself is gated by plan_search --check-plans)")
        return

    json_path = _path_flag("--json")  # machine-readable perf trajectory
    check_path = _path_flag("--check-against")  # perf-regression gate
    write_baseline = _path_flag("--write-baseline")
    plan_archs = _path_flag("--plan-archs")  # scope plan_search families
    check_plans_flag = "--check-plans" in args  # plan snapshot gate
    if check_plans_flag:
        args.remove("--check-plans")
    write_plans_flag = "--write-plans" in args  # plan snapshot refresh
    if write_plans_flag:
        args.remove("--write-plans")
    kv_dtype = _path_flag("--kv-dtype")  # int8: add the quantized workload
    if kv_dtype not in (None, "bf16", "int8"):
        raise SystemExit(f"--kv-dtype must be bf16 or int8, got {kv_dtype}")
    shared_prefix = "--shared-prefix" in args
    if shared_prefix:  # serve_cb --shared-prefix: add the paged workload
        args.remove("--shared-prefix")
    names = args or list(_ORDER)
    if shared_prefix and "serve_paged" not in names:
        names.append("serve_paged")
    if kv_dtype == "int8" and "serve_quant" not in names:
        names.append("serve_quant")
    if (check_plans_flag or write_plans_flag) and "plan_search" not in names:
        names.append("plan_search")
    unknown = [n for n in names if n not in BENCHES]
    if unknown:  # fail before running anything — compiles cost minutes
        raise SystemExit(
            f"unknown benchmark(s) {unknown}; choose from {sorted(BENCHES)}")
    state: Dict = {}
    if plan_archs:
        # CI matrix family names use underscores; arch registry uses dashes
        requested = [a.strip().replace("_", "-")
                     for a in plan_archs.split(",") if a.strip()]
        from repro.configs import list_archs
        bad_archs = [a for a in requested if a not in list_archs()]
        if bad_archs:
            raise SystemExit(f"--plan-archs: unknown arch(es) {bad_archs}")
        state["plan_archs"] = tuple(requested)
    ran = set()
    for name in names:
        for dep in _NEEDS.get(name, []):
            if dep not in ran:
                BENCHES[dep](state)
                ran.add(dep)
        if name not in ran:
            BENCHES[name](state)
            ran.add(name)
    print(f"\n{len(ROWS)} benchmark rows")
    bench_json = state.get("bench_json", {})
    if json_path is not None:
        payload = dict(bench_json, rows=ROWS, _run_meta=_run_meta(state))
        with open(json_path, "w") as f:
            json.dump(payload, f, indent=2, sort_keys=True)
        print(f"wrote {json_path}")
    if write_baseline is not None:
        import os
        # MERGE into an existing baseline: only the sections this run
        # produced are replaced, so the single-device serving refresh and
        # the 8-device serve_sharded refresh compose into one file
        payload: Dict = {}
        if os.path.exists(write_baseline):
            with open(write_baseline) as f:
                payload = json.load(f)
        # `_predicted` stamps are machine-relative model diagnostics —
        # they never enter the committed baseline (the band is enforced
        # per-run, not baseline-relative)
        payload.update(_pop_predicted(bench_json))
        payload["_meta"] = {
            "note": "perf-gate baseline; regenerate ON A QUIET BOX OF THE "
                    "CI RUNNER CLASS with `python benchmarks/run.py "
                    "serve_cb serve_spec --shared-prefix --kv-dtype int8 "
                    "--write-baseline benchmarks/baseline.json` plus "
                    "`XLA_FLAGS=--xla_force_host_platform_device_count=8 "
                    "python benchmarks/run.py serve_sharded "
                    "serve_throughput "
                    "--write-baseline benchmarks/baseline.json` (writes "
                    "MERGE per-section) — or one click via the "
                    "baseline-refresh workflow_dispatch job (absolute "
                    "tok_s is machine-relative; the speedup ratios and "
                    "token_match_rate transfer)",
            "gate": {"tok_s_regression": TOK_S_REGRESSION,
                     "dispatches_per_token_increase": DISP_TOK_INCREASE,
                     "token_match_floor": TOKEN_MATCH_FLOOR,
                     "match_floor_overrides": dict(_MATCH_FLOORS),
                     "ratio_keys": list(RATIO_KEYS)}}
        with open(write_baseline, "w") as f:
            json.dump(payload, f, indent=2, sort_keys=True)
        print(f"wrote baseline {write_baseline}")
    rc = 0
    if write_plans_flag:
        write_plans(state.get("plan_snapshots", {}))
    if check_plans_flag:
        rc = max(rc, check_plans(state.get("plan_snapshots", {})))
    if check_path is not None:
        rc = max(rc, check_against(check_path, bench_json,
                                   ran=ran - state.get("skipped", set())))
    if rc:
        sys.exit(rc)


if __name__ == "__main__":
    main()
