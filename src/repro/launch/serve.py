"""Serving driver CLI: bring up the continuous-batching engine for any
--arch and serve a Poisson request stream (the paper's kind of deployment:
a line-rate ingress feeding a spatial pipeline that never waits for a full
batch, §8.2).

Requests are submitted with exponential inter-arrival gaps and admitted
into freed KV-cache slots between decode steps; weights and the serving
cache are placed under the Cluster-Builder plan:

  --plan serve           kv-head-sharded paged serving over a
                         (data, model) mesh — bit-identical to
                         single-device (docs/serving.md §sharded serving)
  --plan serve_pipeline  layer stack sharded over a `stage` mesh axis,
                         decode micro-steps streamed with
                         collective_permute (the paper's 6-FPGA encoder
                         pipeline)
  --plan none            single-device (debug)

`--no-exact` switches either serve plan to throughput mode: psum-form TP
(serve) or the request-skewed pipeline schedule with stage-local KV
arenas (serve_pipeline) — faster, token streams gated by a match-rate
band instead of bitwise equality (docs/serving.md §exactness contract).

`--dryrun` prints the chosen plan's per-leaf shardings (params + serving
cache) and exits, so a deploy is inspectable before anything runs:

  PYTHONPATH=src python -m repro.launch.serve --arch smollm-135m --reduced \
      --plan serve --mesh 1,8 --dryrun
"""
from __future__ import annotations

import argparse
import time

import numpy as np

import jax

from repro.configs import get_config
from repro.core.cluster_builder import build_plan
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_mesh
from repro.models.transformer import init_params, make_model
from repro.serving.engine import ContinuousBatchingEngine, WaveEngine
from repro.serving.kv_manager import paged_eligible
from repro.serving.stream import (bursty_requests, poisson_requests,
                                  shared_prefix_requests)


def _parse_mesh(spec: str, plan_mode: str):
    """--mesh "1,8" -> (data, model) mesh; --mesh "8" under serve_pipeline
    -> (stage,) mesh.  Default: all visible devices on the plan's TP/stage
    axis."""
    n_dev = jax.device_count()
    if plan_mode == "serve_pipeline":
        shape = tuple(int(x) for x in spec.split(",")) if spec else (n_dev,)
        if len(shape) != 1:
            raise SystemExit("serve: serve_pipeline takes a 1-axis --mesh "
                             "(the stage axis), e.g. --mesh 8")
        return make_mesh(shape, ("stage",))
    shape = tuple(int(x) for x in spec.split(",")) if spec else (1, n_dev)
    if len(shape) != 2:
        raise SystemExit("serve: --plan serve takes a 2-axis --mesh "
                         "(data, model), e.g. --mesh 1,8")
    return make_mesh(shape, ("data", "model"))


def _fleet_plans(cfg, args):
    """Per-replica plans over disjoint device groups: --mesh is the shape
    of ONE replica's mesh (default: an even split of the host), and the
    fleet needs replicas x width devices (serving/replica.py raises
    otherwise)."""
    from repro.serving.replica import make_group_mesh, replica_device_groups
    n = args.replicas
    per = max(jax.device_count() // n, 1)
    if args.plan == "serve_pipeline":
        shape = (tuple(int(x) for x in args.mesh.split(","))
                 if args.mesh else (per,))
        if len(shape) != 1:
            raise SystemExit("serve: serve_pipeline takes a 1-axis --mesh "
                             "(the per-replica stage axis), e.g. --mesh 4")
        axes = ("stage",)
    else:
        shape = (tuple(int(x) for x in args.mesh.split(","))
                 if args.mesh else (1, per))
        if len(shape) != 2:
            raise SystemExit("serve: --plan serve takes a 2-axis --mesh "
                             "(data, model) per replica, e.g. --mesh 1,2")
        axes = ("data", "model")
    width = 1
    for s in shape:
        width *= s
    groups = replica_device_groups(n, width)
    return [build_plan(cfg, make_group_mesh(g, shape, axes),
                       mode=args.plan, exact=args.exact) for g in groups]


# projections that *reduce* over a contracted dim: replicated + gather-form
# under exact serving, column-sharded + psum-form under --no-exact
_REDUCTION_LEAVES = ("wo", "shared_wo", "glu_wo", "down", "w_out")


def _leaf_exactness(plan, path) -> str:
    """Exactness mode of one plan leaf, for --dryrun (docs/serving.md
    §exactness contract)."""
    name = path[-1] if path else ""
    if name in ("q", "scale") and len(path) > 1:  # quantized leaf pair
        name = path[-2]
    if plan.mode == "serve" and name in _REDUCTION_LEAVES:
        return "gather(exact)" if plan.exact else "psum(throughput)"
    if plan.mode == "serve_pipeline":
        return "drained(exact)" if plan.exact else "skewed(throughput)"
    return "exact"


def _print_shardings(title: str, specs, shapes, plan=None) -> None:
    print(f"-- {title} " + "-" * max(1, 60 - len(title)))

    def walk(sp, sh, path=()):
        if isinstance(sp, dict):
            for k in sorted(sp):
                walk(sp[k], sh[k], path + (k,))
            return
        note = f"  [{_leaf_exactness(plan, path)}]" if plan is not None else ""
        print(f"  {'/'.join(path):<40} {str(tuple(sh.shape)):<22} {sp}{note}")

    walk(specs, shapes)


def _print_pareto(cfg, result) -> None:
    """The auto-search dump: profile, frontier table (chosen starred),
    and — when nothing is feasible — the pruning reasons
    (docs/serving.md §plan auto-search)."""
    prof = result.profile
    print(f"plan auto-search: arch={cfg.name} profile={prof.name} "
          f"(rate={prof.arrival_rate:g}/s prompt~{prof.prompt_mean:g} "
          f"out~{prof.output_mean:g} devices={prof.devices} "
          f"hbm={prof.hbm_gb:g}GB max_batch={prof.max_batch})")
    print(f"  {len(result.scores)} candidates, {result.n_feasible} "
          f"feasible, {len(result.frontier)} on the Pareto frontier "
          "(max tok/s, min ttft, min hbm):")
    print(f"  {'candidate':<38} {'tok/s':>9} {'ttft_ms':>9} "
          f"{'hbm':>5} {'lanes':>5} {'repl':>4}")
    for s in result.frontier:
        mark = "*" if result.chosen and s.key == result.chosen.key else " "
        print(f" {mark}{s.key:<38} {s.tok_s:>9.0f} {s.ttft_ms:>9.3f} "
              f"{s.hbm_frac:>5.2f} {s.lanes:>5d} {s.replicas:>4d}")
    if result.chosen is not None:
        print(f"  chosen: {result.chosen.key}")
    else:
        reasons = {}
        for s in result.scores:
            if not s.feasible:
                reasons[s.reason] = reasons.get(s.reason, 0) + 1
        for rsn, n in sorted(reasons.items()):
            print(f"  infeasible x{n}: {rsn}")


def _dryrun(cfg, plan, paged: bool, engine_kw) -> None:
    """Spec-only plan inspection: eval_shape everything, allocate nothing."""
    model = make_model(cfg, remat=False)
    params_shape = jax.eval_shape(
        lambda k: init_params(cfg, k),
        jax.ShapeDtypeStruct((2,), jax.numpy.uint32))
    plan.param_specs = plan.specs_for_params(params_shape)
    print(f"serve --dryrun: arch={cfg.name} mode={plan.mode} "
          f"exact={plan.exact} mesh={dict(plan.mesh.shape)} paged={paged}")
    _print_shardings("params", plan.param_specs, params_shape, plan=plan)
    if paged:
        ps = engine_kw.get("page_size", 16)
        cache_shape = jax.eval_shape(
            lambda: model.init_paged_cache(4, 64, ps, 8,
                                           kv_dtype=engine_kw.get(
                                               "kv_dtype", "bf16")))
    else:
        cache_shape = jax.eval_shape(lambda: model.init_cache(4, 64))
    cache_specs = plan.specs_for_caches(cache_shape, batch=4,
                                        slot_table=True, paged=paged)
    _print_shardings("serving cache" + (" (paged arena)" if paged else
                                        " (dense slots)"),
                     cache_specs, cache_shape, plan=plan)


def main(argv=None):
    """Parse argv, build the engine (or fleet), serve one request stream
    and print its summary.  Returns (finished requests, the engine or
    fleet router that served them)."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-135m")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--rate", type=float, default=0.0,
                    help="Poisson arrival rate (req/s); 0 = all at t=0")
    ap.add_argument("--engine", choices=["cb", "wave"], default="cb")
    ap.add_argument("--decode-horizon", type=int, default=8,
                    help="max fused decode steps per dispatch (1 = the "
                         "one-dispatch-per-token baseline; docs/perf.md)")
    ap.add_argument("--plan",
                    choices=["none", "serve", "serve_pipeline", "auto"],
                    default="serve",
                    help="Cluster-Builder placement mode (docs/serving.md); "
                         "auto = cost-model search over TP width / stage "
                         "depth / exactness / paging knobs for the --traffic "
                         "profile (docs/serving.md §plan auto-search)")
    ap.add_argument("--traffic", default="",
                    help="traffic-profile JSON for --plan auto (arrival "
                         "rate, prompt/output mix, device + HBM budget); "
                         "default: the built-in default profile "
                         "(benchmarks/profiles/default.json mirrors it)")
    ap.add_argument("--mesh", default="",
                    help="mesh shape, e.g. 1,8 for (data, model) or 8 for "
                         "the serve_pipeline stage axis; default spans all "
                         "visible devices")
    ap.add_argument("--exact", dest="exact", action="store_true",
                    default=True,
                    help="bit-identical serving (default): gather-form TP "
                         "and the drained pipeline schedule")
    ap.add_argument("--no-exact", dest="exact", action="store_false",
                    help="throughput mode: psum-form TP (serve) / request-"
                         "skewed schedule with stage-local KV arenas "
                         "(serve_pipeline); token streams are gated by a "
                         "match-rate band, not equality (docs/serving.md "
                         "§exactness contract)")
    ap.add_argument("--dryrun", action="store_true",
                    help="print the chosen plan's per-leaf shardings and "
                         "exactness modes (params + serving cache) and exit")
    ap.add_argument("--no-plan", action="store_true",
                    help="deprecated alias for --plan none")
    ap.add_argument("--stream",
                    choices=["poisson", "shared-prefix", "bursty"],
                    default="poisson",
                    help="shared-prefix: one system prompt + unique tails "
                         "(the radix prefix cache's target ingress); "
                         "bursty: steady short prompts with long-prompt "
                         "bursts (the --disagg pools' target ingress, "
                         "docs/perf.md §TTFT under burst)")
    ap.add_argument("--page-size", type=int, default=16,
                    help="paged-KV page length (rows); paged mode is "
                         "auto-enabled for all-attention models under no "
                         "plan or a serve plan (docs/serving.md)")
    ap.add_argument("--num-pages", type=int, default=0,
                    help="KV page-pool size (0 = match the dense slot "
                         "table's capacity)")
    ap.add_argument("--kv-dtype", choices=["bf16", "int8"], default="bf16",
                    help="paged KV-cache storage dtype; int8 stores "
                         "quantized pages (+ per-row scales) at ~half the "
                         "HBM per token (docs/serving.md §kv_dtype)")
    ap.add_argument("--disagg", default="",
                    help="disaggregated prefill/decode pools as P:D device "
                         "counts, e.g. --disagg 4:4 — devices [0,P) run "
                         "bucketed prefill and ship completed KV pages "
                         "into the decode pool's arena; the radix tree "
                         "spans both, so prefix hits admit decode-side "
                         "with zero transfers.  Needs the paged cb engine "
                         "under --plan none (docs/serving.md "
                         "§disaggregated serving)")
    ap.add_argument("--draft-config", default="",
                    help="arch name for a speculative-decoding draft model "
                         "(randomly initialised; --reduced applies to it "
                         "too).  Enables greedy speculative decoding: the "
                         "draft proposes --spec-k tokens per lane per "
                         "dispatch and the target verifies them in one "
                         "batched pass (docs/serving.md §speculative "
                         "decoding).  Needs the paged cb engine.")
    ap.add_argument("--spec-k", type=int, default=4,
                    help="max drafted tokens per speculative dispatch; the "
                         "scheduler walks a per-lane depth ladder below "
                         "this cap on low acceptance")
    ap.add_argument("--quant-weights", action="store_true",
                    help="serve W8A8: projections/MLP run int8 x int8 -> "
                         "int32 (models/quantized.py); composes with any "
                         "--plan (specs derive from the quantized tree)")
    ap.add_argument("--replicas", type=int, default=0,
                    help="serve a fleet of N independent engine replicas "
                         "behind the prefix-affinity router (docs/fleet.md)."
                         "  0 (default) = 1, or the auto-chosen replica "
                         "count under --plan auto.  With a plan, --mesh is "
                         "per-replica and the fleet needs replicas x width "
                         "devices (disjoint groups).")
    ap.add_argument("--route",
                    choices=["affinity", "round-robin", "least-loaded"],
                    default="affinity",
                    help="fleet dispatch policy (needs --replicas > 1): "
                         "affinity routes each request to the replica whose "
                         "radix tree should hold its longest prefix, "
                         "falling back to least-loaded; round-robin is the "
                         "control arm (docs/fleet.md)")
    ap.add_argument("--shed-depth", type=int, default=0,
                    help="fleet load shedding: reject a request when every "
                         "replica's admission queue is this deep (x the "
                         "--shed-budget multiplier); 0 = never shed")
    ap.add_argument("--shed-budget", type=float, default=1.0,
                    help="deadline-budget multiplier on --shed-depth")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    if args.no_plan:
        args.plan = "none"
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    if args.kv_dtype == "int8" and args.engine != "cb":
        raise SystemExit(
            "serve: --kv-dtype int8 needs the continuous-batching engine "
            "(the wave baseline decodes dense slot rows); drop --engine wave")

    auto_choice = None
    if args.plan == "auto":
        from repro.core.plan_search import TrafficProfile, realize, search
        profile = (TrafficProfile.from_json(args.traffic) if args.traffic
                   else TrafficProfile())
        result = search(cfg, profile)
        _print_pareto(cfg, result)
        if result.chosen is None:
            raise SystemExit(
                "serve: plan auto-search found no feasible candidate for "
                "this traffic profile (pruning reasons above); raise "
                "hbm_gb/devices or quantize")
        auto_choice = result.chosen
        cand = auto_choice.cand
        if cand.paged and args.engine != "cb":
            raise SystemExit("serve: the auto-chosen plan serves from the "
                             "paged pool; drop --engine wave")
        args.plan, args.exact = cand.mode, cand.exact
        if cand.paged:
            args.page_size, args.kv_dtype = cand.page_size, cand.kv_dtype
        args.quant_weights = args.quant_weights or cand.quant_weights

    # --replicas 0 = auto: the plan search's replica count (its explicit
    # TP-width-vs-replica-count axis) when --plan auto chose one, else 1
    if args.replicas == 0:
        args.replicas = (auto_choice.replicas if auto_choice is not None
                         else 1)
    if args.replicas > 1 and args.engine != "cb":
        raise SystemExit("serve: --replicas > 1 serves a fleet of "
                         "continuous-batching engines; drop --engine wave")
    fleet = args.replicas > 1 and not args.dryrun
    if auto_choice is not None and not args.mesh:
        cand = auto_choice.cand
        if fleet:  # per-replica mesh: each engine gets its device group
            args.mesh = (f"1,{cand.tp}" if cand.mode == "serve"
                         else str(cand.stages))
        else:
            args.mesh = (f"{auto_choice.replicas},{cand.tp}"
                         if cand.mode == "serve" else str(cand.stages))

    plan, plans = None, None
    if args.plan != "none":
        if auto_choice is not None and args.dryrun:
            # spec inspection needs no devices: realise on an AbstractMesh
            # of the candidate's own shape (profile.devices may differ
            # from this host)
            plan = realize(cfg, auto_choice)
        elif fleet:
            plans = _fleet_plans(cfg, args)
            plan = plans[0]  # representative: replicas differ only in devices
        else:
            mesh = _parse_mesh(args.mesh, args.plan)
            plan = build_plan(cfg, mesh, mode=args.plan, exact=args.exact)
    # the engine's own paged="auto" predicate, shared so the CLI's int8
    # guard and --dryrun can never disagree with what the engine does
    paged = paged_eligible(cfg, plan) and args.engine == "cb"
    if args.kv_dtype == "int8" and not paged:
        raise SystemExit(
            "serve: --kv-dtype int8 needs the paged pool (all-attention "
            "model under --plan none, serve, or a --no-exact "
            "serve_pipeline)")
    disagg = None
    if args.disagg:
        try:
            p_pool, d_pool = (int(x) for x in args.disagg.split(":"))
        except ValueError:
            raise SystemExit("serve: --disagg takes P:D device counts, "
                             "e.g. --disagg 4:4")
        if args.engine != "cb" or not paged:
            raise SystemExit("serve: --disagg needs the paged cb engine "
                             "(page shipping is the handoff mechanism)")
        if args.plan != "none":
            raise SystemExit("serve: --disagg owns device placement; "
                             "combine it with --plan none")
        if args.replicas > 1:
            raise SystemExit("serve: --disagg does not compose with a "
                             "--replicas fleet yet (cross-host shipping "
                             "lands with the multi-process fleet)")
        if args.draft_config:
            raise SystemExit("serve: --disagg does not compose with "
                             "--draft-config (no draft shipping path yet)")
        disagg = (p_pool, d_pool)
    if args.dryrun:
        if plan is None:
            raise SystemExit("serve: --dryrun inspects a plan; pick "
                             "--plan serve or serve_pipeline")
        _dryrun(cfg, plan, paged,
                dict(page_size=args.page_size, kv_dtype=args.kv_dtype))
        return [], None

    model = make_model(cfg, remat=False)
    params = init_params(cfg, jax.random.PRNGKey(args.seed))
    cls = ContinuousBatchingEngine if args.engine == "cb" else WaveEngine
    kw = {}
    if cls is ContinuousBatchingEngine:
        kw["page_size"] = args.page_size
        kw["kv_dtype"] = args.kv_dtype
        if disagg is not None:
            kw["disagg"] = disagg
        if args.num_pages:
            kw["num_pages"] = args.num_pages
        if args.draft_config:
            if not paged:
                raise SystemExit(
                    "serve: --draft-config needs the paged cb engine "
                    "(all-attention model under --plan none or serve)")
            dcfg = get_config(args.draft_config)
            if args.reduced:
                dcfg = dcfg.reduced()
            if dcfg.vocab_size != cfg.vocab_size:
                raise SystemExit(
                    "serve: draft and target must share a vocabulary "
                    f"({dcfg.vocab_size} vs {cfg.vocab_size})")
            draft_model = make_model(dcfg, remat=False)
            draft_params = init_params(dcfg, jax.random.PRNGKey(args.seed + 1))
            kw["spec_config"] = dict(draft_model=draft_model,
                                     draft_params=draft_params,
                                     spec_k=args.spec_k)
    elif args.draft_config:
        raise SystemExit("serve: --draft-config needs --engine cb")
    max_batch = args.max_batch
    if (plan is not None and plan.mode == "serve_pipeline"
            and not plan.exact and cls is ContinuousBatchingEngine):
        n_stages = plan.mesh.shape[plan.axes.stage]
        if max_batch % n_stages:
            max_batch = -(-max_batch // n_stages) * n_stages
            print(f"serve: request-skewed pipeline needs one lane group "
                  f"per stage; max_batch {args.max_batch} -> {max_batch} "
                  f"({n_stages} stages)")
    if fleet:
        from repro.serving.router import FleetConfig, build_fleet
        router = build_fleet(
            model, params, args.replicas, plans=plans,
            config=FleetConfig(route=args.route,
                               shed_depth=args.shed_depth,
                               shed_budget=args.shed_budget),
            max_batch=max_batch, buckets=(16, 32, 64, 128),
            decode_horizon=args.decode_horizon,
            quant_weights=args.quant_weights, **kw)
        engine = router
    else:
        engine = cls(model, params, max_batch=max_batch,
                     buckets=(16, 32, 64, 128), plan=plan,
                     decode_horizon=args.decode_horizon,
                     quant_weights=args.quant_weights, **kw)

    rng = np.random.default_rng(args.seed)
    t0 = time.perf_counter()
    if args.stream == "shared-prefix":
        stream = shared_prefix_requests(rng, args.requests, cfg.vocab_size,
                                        prefix_len=48, suffix_range=(3, 9),
                                        budgets=args.max_new,
                                        rate=args.rate)
    elif args.stream == "bursty":
        stream = bursty_requests(rng, args.requests, cfg.vocab_size,
                                 budgets=args.max_new, rate=args.rate)
    else:
        stream = poisson_requests(rng, args.requests, cfg.vocab_size,
                                  len_range=(4, 60), budgets=args.max_new,
                                  rate=args.rate)
    for r in stream:
        engine.submit(r)
    done = engine.run()
    wall = time.perf_counter() - t0

    toks = sum(len(r.tokens_out) for r in done)
    if fleet:
        st = engine.stats()
        print(f"serve[fleet]: arch={cfg.name} plan={args.plan} "
              f"replicas={args.replicas} route={args.route} "
              f"requests={len(done)} shed={st['shed']} tokens={toks} "
              f"wall={wall*1e3:.0f}ms throughput={toks/max(wall, 1e-9):.1f}"
              f"tok/s by_kind={st['by_kind']} "
              f"prefix_hit_tokens={st['prefix_hit_tokens']}")
        for p in st["replicas"]:
            print(f"  replica {p['replica']}: routed={p['routed']} "
                  f"admitted={p.get('admitted', 0)} "
                  f"hit_rate={p['prefix_hit_rate']:.2f} "
                  f"wall={p['wall_s']*1e3:.0f}ms")
        for req, reason in engine.shed[:3]:
            print(f"  shed rid={req.rid}: {reason}")
        return done, engine
    lat = sorted((r.t_done - r.t_enqueue) * 1e3 for r in done)
    ttft = sorted((r.t_first_token - r.t_enqueue) * 1e3 for r in done)
    print(f"serve[{args.engine}]: arch={cfg.name} plan={args.plan} "
          f"requests={len(done)} tokens={toks} wall={wall*1e3:.0f}ms "
          f"throughput={toks/wall:.1f}tok/s "
          f"ttft_p50={ttft[len(ttft)//2]:.0f}ms "
          f"p50={lat[len(lat)//2]:.0f}ms p_max={lat[-1]:.0f}ms "
          f"stats={engine.stats}")
    return done, engine


if __name__ == "__main__":
    main()
