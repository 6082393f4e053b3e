"""Throughput-mode serving (exact=False plans): psum-form TP specs, the
request-skewed pipeline schedule, lane-group scheduling, and the
stage-local KV accounting behind it.

The exactness contract (docs/serving.md): exact plans stay bit-identical
and their tests (tests/test_sharded_serving.py) are untouched; throughput
plans are gated by a token-match band (>=0.98) instead of equality.  The
multi-device case runs in a subprocess with
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (same pattern as
tests/test_pipeline.py); everything else is host-side (scheduler /
kv-manager / spec rules) and needs no devices.
"""
import os
import subprocess
import sys
import textwrap

import numpy as np

import pytest


def _run(script: str, n_dev: int = 8) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_dev}"
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(script)],
                         capture_output=True, text=True, env=env,
                         timeout=900)
    assert out.returncode == 0, out.stdout + "\n" + out.stderr
    return out.stdout


# -- scheduler: lane groups ------------------------------------------------


def test_lane_groups_partition_and_admission_balance():
    """Lanes partition into equal contiguous groups, and `order_free`
    round-robins a burst of admissions across the emptiest groups instead
    of packing the first group solid."""
    from repro.serving.scheduler import Request, Scheduler

    s = Scheduler((16,), 0.0, decode_horizon=8, max_batch=8)
    s.set_lane_groups(4)
    groups = {g: [i for i in range(8) if s.lane_group(i) == g]
              for g in range(4)}
    assert groups == {0: [0, 1], 1: [2, 3], 2: [4, 5], 3: [6, 7]}
    assert sorted(sum(groups.values(), [])) == list(range(8))  # disjoint

    # group 0 fully occupied, group 2 half occupied, groups 1/3 empty:
    # the free-slot order must visit every emptier group before giving
    # group 2 a second occupant, and group 0 has nothing free at all
    r = Request(rid=0, prompt=np.zeros(2, np.int32))
    slots = [r, r, None, None, r, None, None, None]
    free = s.order_free([i for i, x in enumerate(slots) if x is None],
                        slots)
    assert free[:2] == [2, 6]  # first pass: one slot per empty group
    assert free[2] == 3 or free[2] == 7 or free[2] == 5
    assert sorted(free) == [2, 3, 5, 6, 7]  # a permutation, nothing lost
    # a burst into an empty batch round-robins all four groups first
    free = s.order_free(list(range(8)), [None] * 8)
    assert free[:4] == [0, 2, 4, 6]
    assert free[4:] == [1, 3, 5, 7]
    # degenerate single group: order untouched
    s2 = Scheduler((16,), 0.0, 8, 8)
    assert s2.order_free([3, 1, 2], [None] * 8) == [3, 1, 2]
    # indivisible partitions are rejected
    with pytest.raises(AssertionError):
        s.set_lane_groups(3)


def test_lane_groups_under_admission_preemption_churn():
    """Drive the real admission cycle with completions and preemptions:
    admissions always land on the group-balanced prefix of the free list,
    no lane starves, and the drain terminates."""
    from repro.serving.scheduler import Request, Scheduler

    rng = np.random.default_rng(0)
    s = Scheduler((16,), 0.05, decode_horizon=8, max_batch=8)
    s.set_lane_groups(4)
    reqs = [Request(rid=i, prompt=np.zeros(3, np.int32),
                    max_new_tokens=int(rng.integers(1, 6)),
                    t_arrival=i * 0.001) for i in range(40)]
    pending = list(reqs)
    slots = [None] * 8
    done, lanes_used = [], set()
    now, steps = 0.0, 0
    while (pending or any(x is not None for x in slots)) and steps < 2000:
        steps += 1
        now += 0.01
        free = s.order_free([i for i, x in enumerate(slots) if x is None],
                            slots)
        admitted, _ = s.admission_cycle(pending, list(free), now, (),
                                        lambda r, sl: True)
        # admission_cycle pops the ordered free list front-to-back, so the
        # slots it filled must be exactly the balanced prefix
        assert [sl for _, sl in admitted] == free[:len(admitted)]
        for r, sl in admitted:
            pending.remove(r)
            slots[sl] = r
            lanes_used.add(sl)
        for i, r in enumerate(slots):  # one decode step per occupied lane
            if r is None:
                continue
            r.append_token(7, now)
            if r.done:
                done.append(r)
                slots[i] = None
        if steps % 5 == 0:  # periodic pool-pressure preemption
            v = s.victim(slots)
            if v is not None:
                r = slots[v]
                r.n_preempts += 1
                slots[v] = None
                pending.append(r)
    assert steps < 2000, "drain did not terminate"
    assert len(done) == len(reqs) and all(r.done for r in reqs)
    assert lanes_used == set(range(8)), f"starved lanes: " \
        f"{set(range(8)) - lanes_used}"


# -- kv-manager: per-shard residency + stage views -------------------------


def test_kv_page_bytes_stage_sharding():
    """A stage-sharded arena page costs 1/shards of the layer stack per
    device — but ONLY when the stack divides; otherwise the arena
    replicates and a page costs its full span everywhere."""
    import dataclasses

    from repro.configs import get_config
    from repro.serving.kv_manager import kv_page_bytes, num_pages_for_hbm

    cfg = dataclasses.replace(get_config("smollm-135m").reduced(),
                              n_layers=8)
    full = kv_page_bytes(cfg, 16, "bf16")
    assert kv_page_bytes(cfg, 16, "bf16", shards=4) == full // 4
    assert kv_page_bytes(cfg, 16, "int8", shards=8) \
        == kv_page_bytes(cfg, 16, "int8") // 8
    # 8 layers over 3 stages don't divide: replicated, full cost
    assert kv_page_bytes(cfg, 16, "bf16", shards=3) == full
    budget = 64 * full
    assert num_pages_for_hbm(cfg, 16, "bf16", budget) == 64
    assert num_pages_for_hbm(cfg, 16, "bf16", budget, shards=4) == 256
    assert num_pages_for_hbm(cfg, 16, "bf16", budget, shards=3) == 64


def test_kv_manager_per_shard_ledger_tracks_actual_frees():
    """The per-shard residency ledger moves by the pages each operation
    ACTUALLY freed (shared prefix pages stay resident through a decref),
    stage views report stage-local bytes, and `assert_drained`
    cross-checks every shard against the pool — no cross-stage leaks."""
    import dataclasses

    from repro.configs import get_config
    from repro.serving.kv_manager import KVManager, kv_page_bytes

    kv = KVManager(num_pages=9, page_size=4, max_batch=4, max_pages=8,
                   shards=4)
    prompt = np.arange(11, dtype=np.int32)
    g = kv.admit(prompt, rem_budget=5, max_hit_suffix=16)  # 16 pos -> 4 pg
    assert g is not None and g.hit_len == 0
    kv.commit(0, g)
    kv.register_prefix(prompt, g.pages)
    assert (kv._shard_pages == kv.pool.pages_in_use).all()
    # a hit shares the 2 full prefix pages: only the remainder is new,
    # and every shard's ledger moves by the same (actual) amount
    before = kv.shard_pages_in_use()
    g2 = kv.admit(prompt, rem_budget=5, max_hit_suffix=16)
    assert g2.hit_len == 8 and g2.pages[:2] == g.pages[:2]
    kv.commit(1, g2)
    grew = kv.shard_pages_in_use() - before
    assert grew == len(g2.pages) - len(g2.hit_pages)
    assert (kv._shard_pages == kv.pool.pages_in_use).all()
    # release lane 1: the shared prefix pages are still held by lane 0 +
    # the tree, so the ledger drops by the exclusively-owned pages only
    before = kv.shard_pages_in_use()
    kv.release(1)
    assert before - kv.shard_pages_in_use() == grew
    assert (kv._shard_pages == kv.pool.pages_in_use).all()
    kv.release(0)
    # only tree references remain; every shard agrees with the pool
    kv.assert_drained()
    # stage views: stage-local byte accounting at 1/shards per page
    cfg = dataclasses.replace(get_config("smollm-135m").reduced(),
                              n_layers=8)
    v = kv.stage_view(2)
    assert v.pages_in_use == kv.pool.pages_in_use
    assert v.resident_bytes(cfg) == v.pages_in_use * kv_page_bytes(
        cfg, 4, "bf16", shards=4)
    # eviction under pool pressure frees tree pages on EVERY shard; the
    # declined prefix hit (suffix > max_hit_suffix) also exercises the
    # actual-frees rule — its decref frees nothing (tree refs remain)
    big = kv.admit(np.arange(28, dtype=np.int32), rem_budget=0,
                   max_hit_suffix=0)  # 7 pages > 6 free: tree must evict
    assert big is not None
    assert (kv._shard_pages == kv.pool.pages_in_use).all()
    kv.commit(3, big)
    kv.release(3)
    kv.assert_drained()
    # a cross-stage leak (one shard's slab stranded) fails loudly
    kv._shard_pages[1] += 1
    with pytest.raises(AssertionError):
        kv.assert_drained()


def test_evict_cached_moves_every_shard_ledger_by_actual_frees():
    """`evict_cached` is the only correct external eviction path: it
    routes the pages the tree ACTUALLY freed through the per-shard
    residency ledger.  Lane-held prefix pages are not evictable (the
    tree's LRU only frees tree-only leaves), so the returned count can
    undershoot the request — and the ledger must move by that count on
    every shard, never by the requested figure."""
    import dataclasses

    from repro.configs import get_config
    from repro.serving.kv_manager import KVManager, kv_page_bytes

    kv = KVManager(num_pages=9, page_size=4, max_batch=4, max_pages=8,
                   shards=4)
    held = np.arange(8, dtype=np.int32)        # 2 full pages, stays laned
    g1 = kv.admit(held, rem_budget=0, max_hit_suffix=16)
    kv.commit(0, g1)
    kv.register_prefix(held, g1.pages)
    idle = np.arange(100, 108, dtype=np.int32)  # 2 full pages, tree-only
    g2 = kv.admit(idle, rem_budget=0, max_hit_suffix=16)
    kv.commit(1, g2)
    kv.register_prefix(idle, g2.pages)
    kv.release(1)
    before = kv.shard_pages_in_use(0)
    # ask for 4: only `idle`'s 2 pages are evictable (lane 0 still holds
    # `held`'s, so the tree drops at most its own leaf refs there)
    freed = kv.evict_cached(4)
    assert freed == len(g2.pages) == 2
    for shard in range(kv.shards):
        assert before - kv.shard_pages_in_use(shard) == freed
    assert (kv._shard_pages == kv.pool.pages_in_use).all()
    # stage views observe the eviction in stage-local bytes
    cfg = dataclasses.replace(get_config("smollm-135m").reduced(),
                              n_layers=8)
    v = kv.stage_view(3)
    assert v.pages_in_use == kv.pool.pages_in_use
    assert v.resident_bytes(cfg) == v.pages_in_use * kv_page_bytes(
        cfg, 4, "bf16", shards=4)
    # the evicted prefix is really gone (cold again), the held one hits
    assert kv.peek_hit(np.arange(100, 109, dtype=np.int32)) == 0
    assert kv.peek_hit(np.arange(9, dtype=np.int32)) == 8
    # drain: release the held lane, evict the remainder, ledgers at zero
    kv.release(0)
    assert kv.evict_cached(kv.pool.num_pages) == len(g1.pages)
    assert kv.pool.pages_in_use == 0
    assert (kv._shard_pages == 0).all()
    kv.assert_drained()


# -- cluster-builder: the exact flag ---------------------------------------


def test_serve_param_specs_psum_form_when_not_exact():
    """exact=True serve plans replicate the reduction projections
    (gather-form TP, bit-identical); exact=False column-shards them over
    `model` — Megatron psum-form (spec-only, abstract mesh)."""
    import dataclasses

    import jax

    from repro.configs import get_config
    from repro.core.cluster_builder import build_plan
    from repro.launch.mesh import make_abstract_mesh
    from repro.models.transformer import init_params, make_model

    cfg = dataclasses.replace(get_config("smollm-135m").reduced(),
                              n_heads=8, n_kv_heads=8)
    make_model(cfg, remat=False)
    params_shape = jax.eval_shape(
        lambda k: init_params(cfg, k),
        jax.ShapeDtypeStruct((2,), jax.numpy.uint32))
    mesh = make_abstract_mesh((1, 8), ("data", "model"))
    exact = build_plan(cfg, mesh, mode="serve")
    assert exact.exact
    es = exact.specs_for_params(params_shape)
    assert all(p is None for p in es["scan"]["b0"]["mix"]["wo"])
    assert all(p is None for p in es["scan"]["b0"]["ffn"]["wo"])
    psum = build_plan(cfg, mesh, mode="serve", exact=False)
    assert not psum.exact
    ps = psum.specs_for_params(params_shape)
    # scan leaves are (n_rep, in, out): column dim (in) shards over model
    assert ps["scan"]["b0"]["mix"]["wo"][1] == "model"
    assert ps["scan"]["b0"]["ffn"]["wo"][1] == "model"
    # non-reduction projections keep the same row sharding either way
    assert ps["scan"]["b0"]["mix"]["wq"] == es["scan"]["b0"]["mix"]["wq"]
    # throughput serve_pipeline: paged arena leaves shard over `stage`
    from repro.models.transformer import make_model as _mm
    pcfg = dataclasses.replace(cfg, n_layers=4)
    model = _mm(pcfg, remat=False)
    smesh = make_abstract_mesh((4,), ("stage",))
    skew = build_plan(pcfg, smesh, mode="serve_pipeline", exact=False)
    shape = jax.eval_shape(
        lambda: model.init_paged_cache(4, 32, 8, 8))
    specs = skew.specs_for_caches(shape, batch=4, paged=True)
    assert specs["scan"]["b0"]["kv"][0] == "stage"
    assert specs["scan"]["b0"]["kpos"][0] == "stage"
    assert all(p is None for p in specs["pt"])  # shared routing metadata
    assert all(p is None for p in specs["pos"])


def test_paged_eligible_throughput_pipeline():
    """The paged predicate: exact serve_pipeline streams the dense slot
    path; the throughput (exact=False) plan decodes from stage-local
    paged arenas."""
    import dataclasses

    from repro.configs import get_config
    from repro.core.cluster_builder import build_plan
    from repro.launch.mesh import make_abstract_mesh
    from repro.serving.kv_manager import paged_eligible

    cfg = dataclasses.replace(get_config("smollm-135m").reduced(),
                              n_layers=4)
    mesh = make_abstract_mesh((4,), ("stage",))
    assert not paged_eligible(cfg, build_plan(cfg, mesh,
                                              mode="serve_pipeline"))
    assert paged_eligible(cfg, build_plan(cfg, mesh, mode="serve_pipeline",
                                          exact=False))


# -- tentpole: the request-skewed schedule (8 host devices) ----------------


def test_skewed_pipeline_streams_within_match_band():
    """exact=False serve_pipeline on an 8-stage mesh: the request-skewed
    engine's streams match the plan-free paged engine's within the
    exactness contract's 0.98 band (with the pinned ref kernels they are
    in fact identical), lane groups are active, and the stage-local
    arenas drain leak-free."""
    _run("""
    import dataclasses
    import numpy as np
    import jax

    from repro.configs import get_config
    from repro.core.cluster_builder import build_plan
    from repro.kernels import ops as kops
    from repro.launch.mesh import make_mesh
    from repro.models.transformer import init_params, make_model
    from repro.serving.engine import ContinuousBatchingEngine, Request

    assert jax.device_count() == 8
    cfg = dataclasses.replace(get_config("smollm-135m").reduced(),
                              n_layers=8)
    model = make_model(cfg, remat=False)
    params = init_params(cfg, jax.random.PRNGKey(0))
    mesh = make_mesh((8,), ("stage",))
    plan = build_plan(cfg, mesh, mode="serve_pipeline", exact=False)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, k).astype(np.int32)
               for k in (5, 9, 12, 6, 8, 11, 7, 10, 4, 13)]
    budgets = [3, 8, 5, 6, 4, 7, 2, 9, 5, 6]

    def run(plan_, **kw):
        eng = ContinuousBatchingEngine(model, params, max_batch=8,
                                       buckets=(16,), plan=plan_,
                                       page_size=8, **kw)
        for i, p in enumerate(prompts):
            eng.submit(Request(rid=i, prompt=p,
                               max_new_tokens=budgets[i]))
        return {r.rid: r.tokens_out for r in eng.run()}, eng

    with kops.pinned_impl("ref"):
        ref, _ = run(None)
        skew, eng = run(plan)
    assert eng.paged, "throughput pipeline must serve from the paged arena"
    assert eng.sched.n_lane_groups == 8, eng.sched.n_lane_groups
    assert eng.kv.shards == 8
    # run() already called kv.assert_drained(): per-stage ledgers agree
    tot = sum(len(v) for v in ref.values())
    matched = sum(sum(a == b for a, b in zip(ref[r], skew[r])) for r in ref)
    rate = matched / tot
    assert rate >= 0.98, (rate, ref, skew)
    print(f"SKEW-MATCH {matched}/{tot}")
    """)


def test_skewed_pipeline_rejects_spec_config():
    """Speculative decoding has no skewed-schedule program: composing it
    with a throughput serve_pipeline plan must fail loudly at
    construction, not decode garbage."""
    _run("""
    import dataclasses
    import jax

    from repro.configs import get_config
    from repro.core.cluster_builder import build_plan
    from repro.launch.mesh import make_mesh
    from repro.models.transformer import init_params, make_model
    from repro.serving.engine import ContinuousBatchingEngine

    cfg = dataclasses.replace(get_config("smollm-135m").reduced(),
                              n_layers=4)
    dcfg = dataclasses.replace(cfg, name=cfg.name + "-draft", n_layers=1)
    model = make_model(cfg, remat=False)
    params = init_params(cfg, jax.random.PRNGKey(0))
    draft = make_model(dcfg, remat=False)
    dparams = init_params(dcfg, jax.random.PRNGKey(1))
    plan = build_plan(cfg, make_mesh((4,), ("stage",)),
                      mode="serve_pipeline", exact=False)
    try:
        ContinuousBatchingEngine(
            model, params, max_batch=4, buckets=(16,), plan=plan,
            spec_config=dict(draft_model=draft, draft_params=dparams,
                             spec_k=4))
    except ValueError as e:
        assert "spec_config" in str(e)
        print("SPEC-REJECT-OK")
    else:
        raise AssertionError("skewed plan + spec_config must raise")
    """, n_dev=4)


def test_serve_dryrun_prints_exactness_modes():
    """launch/serve.py --no-exact --dryrun: the header carries the exact
    flag and every plan leaf is annotated with its exactness mode."""
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = src
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.serve", "--arch",
         "smollm-135m", "--reduced", "--plan", "serve", "--mesh", "1,8",
         "--no-exact", "--dryrun"], capture_output=True, text=True,
        env=env, timeout=300)
    assert out.returncode == 0, out.stdout + "\n" + out.stderr
    assert "exact=False" in out.stdout
    assert "[psum(throughput)]" in out.stdout  # the reduction projections
    assert "[exact]" in out.stdout  # everything else
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.serve", "--arch",
         "smollm-135m", "--reduced", "--plan", "serve_pipeline", "--mesh",
         "2", "--no-exact", "--dryrun"], capture_output=True, text=True,
        env=env, timeout=300)
    assert out.returncode == 0, out.stdout + "\n" + out.stderr
    assert "exact=False" in out.stdout
    assert "[skewed(throughput)]" in out.stdout
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.serve", "--arch",
         "smollm-135m", "--reduced", "--plan", "serve", "--mesh", "1,8",
         "--dryrun"], capture_output=True, text=True, env=env, timeout=300)
    assert out.returncode == 0, out.stdout + "\n" + out.stderr
    assert "exact=True" in out.stdout
    assert "[gather(exact)]" in out.stdout
