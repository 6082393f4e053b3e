"""Sharded serving: the scheduler/executor/kv-manager split and the
plan-sharded decode paths.

Multi-device cases run in subprocesses with
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (the flag must be
set before jax init — same pattern as tests/test_pipeline.py) and assert
the tentpole contracts: a ``mode="serve"`` plan shards the paged arena's
kv-head dim across the mesh with BIT-IDENTICAL token streams (bf16 and
int8, cold and prefix-hit), and a ``mode="serve_pipeline"`` plan streams
decode through the stage axis bit-identically to ``Model.decode_steps``.
Host-side layer tests (no devices) cover the split's independence.
"""
import os
import subprocess
import sys
import textwrap

import numpy as np


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


# -- the three layers stand alone ------------------------------------------


def test_scheduler_and_kv_manager_import_without_jax():
    """Acceptance: the host-side layers are importable (and constructible)
    independently — no jax in the process."""
    out = subprocess.run([sys.executable, "-c", textwrap.dedent("""
        import sys
        sys.path.insert(0, %r)
        from repro.serving.scheduler import Request, Scheduler
        from repro.serving.kv_manager import KVManager, kv_page_bytes
        s = Scheduler((16, 32), 0.05, 8, 4)
        kv = KVManager(num_pages=9, page_size=4, max_batch=2, max_pages=4)
        assert "jax" not in sys.modules, "host layers must not pull jax"
        print("NOJAX-OK")
    """ % os.path.join(os.path.dirname(__file__), "..", "src"))],
        capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stdout + "\n" + out.stderr
    assert "NOJAX-OK" in out.stdout


def test_scheduler_horizon_ladder_standalone():
    from repro.serving.scheduler import Scheduler

    s = Scheduler((16, 32), 0.05, decode_horizon=8, max_batch=4)
    assert s.horizons == [1, 2, 4, 8]
    # waiting: floor 4, aim at min remaining
    assert s.pick_horizon(True, [7, 12]) == 4
    assert s.pick_horizon(True, [1]) == 4  # floored
    assert s.pick_horizon(False, [3, 9]) == 8  # drained: run long
    assert Scheduler((16,), 0.0, 1, 1).pick_horizon(False, [64]) == 1


def test_kv_manager_grant_and_release_standalone():
    from repro.serving.kv_manager import KVManager

    kv = KVManager(num_pages=9, page_size=4, max_batch=2, max_pages=6)
    prompt = np.arange(9, dtype=np.int32)
    g = kv.admit(prompt, rem_budget=4, max_hit_suffix=16)  # 13 pos -> 4 pg
    assert g is not None and len(g.pages) == 4 and g.hit_len == 0
    assert g.pt_row[:4].tolist() == g.pages and g.pt_row[4:].tolist() == [0, 0]
    kv.commit(0, g)
    kv.register_prefix(prompt, g.pages)  # 2 full pages registered
    assert kv.prefix_cache.cached_pages == 2
    # second identical prompt hits the 2-page prefix (8 of 9 tokens)
    g2 = kv.admit(prompt, rem_budget=4, max_hit_suffix=16)
    assert g2 is not None and g2.hit_len == 8
    assert g2.pages[:2] == g.pages[:2]  # shared, copy-free
    kv.commit(1, g2)
    kv.release(0)
    kv.release(1)
    kv.assert_drained()  # only tree references remain


def test_paged_arena_specs_kv_head_sharded():
    """Cluster-Builder paged leaf rules: the bf16 arena's [k_h | v_h]
    rows, the int8 arena's k/v and its scale planes shard the kv heads
    over `model`; kpos/pt/pos replicate (spec-only, abstract mesh — no
    devices needed)."""
    import dataclasses

    import jax

    from repro.configs import get_config
    from repro.core.cluster_builder import build_plan
    from repro.launch.mesh import make_abstract_mesh
    from repro.models.transformer import make_model

    cfg = dataclasses.replace(get_config("smollm-135m").reduced(),
                              n_heads=8, n_kv_heads=8)
    model = make_model(cfg, remat=False)
    mesh = make_abstract_mesh((1, 8), ("data", "model"))
    plan = build_plan(cfg, mesh, mode="serve")
    shape = jax.eval_shape(
        lambda: model.init_paged_cache(4, 32, 8, 8, kv_dtype="int8"))
    specs = plan.specs_for_caches(shape, batch=4, paged=True)
    b0 = specs["scan"]["b0"]
    assert b0["k"][3] == "model" and b0["v"][3] == "model"
    assert b0["k_scale"][3] == "model" and b0["v_scale"][3] == "model"
    assert all(p is None for p in b0["kpos"])
    assert all(p is None for p in specs["pt"])
    assert all(p is None for p in specs["pos"])
    bf16 = plan.specs_for_caches(jax.eval_shape(
        lambda: model.init_paged_cache(4, 32, 8, 8)), batch=4, paged=True)
    assert bf16["scan"]["b0"]["kv"][3] == "model"
    assert all(p is None for p in bf16["scan"]["b0"]["kpos"])
    # indivisible kv heads fall back to replication, never uneven shards
    cfg3 = dataclasses.replace(cfg, n_heads=9, n_kv_heads=3)
    model3 = make_model(cfg3, remat=False)
    shape3 = jax.eval_shape(
        lambda: model3.init_paged_cache(4, 32, 8, 8))
    specs3 = build_plan(cfg3, mesh, mode="serve").specs_for_caches(
        shape3, batch=4, paged=True)
    assert all(p is None for p in specs3["scan"]["b0"]["kv"])


# -- tentpole: sharded-vs-unsharded bit identity (8 host devices) ----------


def test_sharded_serve_bit_identical_bf16_int8_and_prefix_hits():
    """serve-mode plan on a (1, 8) mesh: the paged engine's token streams
    — bf16 and int8, cold and via radix prefix hits on the sharded arena —
    are bit-identical to the single-device paged engine's."""
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
                              n_heads=8, n_kv_heads=8)
    model = make_model(cfg, remat=False)
    params = init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(3)
    sys_prompt = rng.integers(0, cfg.vocab_size, 35).astype(np.int32)

    def reqs():
        out = []
        for i in range(5):
            tail = np.random.default_rng(100 + i).integers(
                0, cfg.vocab_size, 4).astype(np.int32)
            out.append(Request(rid=i,
                               prompt=np.concatenate([sys_prompt, tail]),
                               max_new_tokens=4 + i % 3))
        return out

    def streams(eng):
        for r in reqs():
            eng.submit(r)
        return {r.rid: tuple(r.tokens_out) for r in eng.run()}

    mesh = make_mesh((1, 8), ("data", "model"))
    plan = build_plan(cfg, mesh, mode="serve")
    with kops.pinned_impl("ref"):
        for kv_dtype in ("bf16", "int8"):
            single = ContinuousBatchingEngine(
                model, params, max_batch=2, buckets=(48,),
                max_decode_len=16, kv_dtype=kv_dtype)
            shard = ContinuousBatchingEngine(
                model, params, max_batch=2, buckets=(48,),
                max_decode_len=16, kv_dtype=kv_dtype, plan=plan)
            assert shard.paged and shard.plan is plan
            # pass 1: cold prefills on both
            assert streams(single) == streams(shard), kv_dtype
            # pass 2: every admission after the first is a radix hit ON
            # THE SHARDED ARENA; streams must still match bit-for-bit
            s1, s2 = streams(single), streams(shard)
            assert s1 == s2, (kv_dtype, s1, s2)
            assert shard.stats["prefix_hits"] >= 4, shard.stats
            # the arena is REALLY distributed: kv heads on `model`
            k = shard._slot_caches["scan"]["b0"][
                "k" if kv_dtype == "int8" else "kv"]
            assert k.sharding.spec[3] == "model", k.sharding.spec
            if kv_dtype == "int8":
                ks = shard._slot_caches["scan"]["b0"]["k_scale"]
                assert ks.sharding.spec[3] == "model", ks.sharding.spec
            print(f"SHARDED-{kv_dtype}-OK")
    """)


def test_serve_pipeline_matches_decode_steps():
    """serve_pipeline plan on a 4-stage mesh: the executor's
    collective_permute-streamed decode program and the engine built on it
    emit exactly what single-device `Model.decode_steps` emits."""
    _run("""
    import dataclasses
    import numpy as np
    import jax, jax.numpy as jnp

    from repro.configs import get_config
    from repro.core.cluster_builder import build_plan
    from repro.kernels import ops as kops
    from repro.launch.mesh import make_mesh
    from repro.models.transformer import init_params, make_model
    from repro.serving.engine import ContinuousBatchingEngine, Request
    from repro.serving.executor import Executor

    cfg = dataclasses.replace(get_config("smollm-135m").reduced(),
                              n_layers=4)
    model = make_model(cfg, remat=False)
    params = init_params(cfg, jax.random.PRNGKey(0))
    mesh = make_mesh((4,), ("stage",))
    plan = build_plan(cfg, mesh, mode="serve_pipeline")
    rng = np.random.default_rng(0)

    with kops.pinned_impl("ref"):
        # executor-level: pipelined fused loop == Model.decode_steps
        B, L = 8, 24
        ex = Executor(model, params, plan=plan, max_batch=B, cache_len=L,
                      buckets=(16,))
        st = ex.fresh_state(ex.init_caches(False), paged=False)
        tok0 = np.zeros(B, np.int32)
        for sl in range(B):
            p = rng.integers(0, cfg.vocab_size, 5 + sl).astype(np.int32)
            logits, small = ex.prefill_prompts([p], 1, bucket_cache=True)
            st["caches"] = ex.insert(st["caches"], small, sl)
            tok0[sl] = int(jnp.argmax(logits[0]))
            ex.admit_lane(st, sl, int(tok0[sl]), -1, 5 + sl % 3)
        ref_caches = jax.tree.map(jnp.asarray,
                                  jax.device_get(st["caches"]))
        toks_ref, *_ = model.decode_steps(
            params, ref_caches, jnp.asarray(tok0), st["active"], 8,
            eos_id=st["eos"], budget=st["budget"], pad_token=0)
        toks_pipe = ex.decode(st, 8, paged=False)
        assert np.array_equal(np.asarray(toks_ref), np.asarray(toks_pipe))
        print("PIPE-EXEC-OK")

        # engine-level: serve_pipeline streams == plan-free dense streams
        prompts = [rng.integers(0, cfg.vocab_size, k).astype(np.int32)
                   for k in (5, 9, 12, 6, 8)]
        budgets = [3, 8, 5, 6, 4]

        def run(plan_):
            eng = ContinuousBatchingEngine(model, params, max_batch=4,
                                           buckets=(16,), plan=plan_,
                                           paged=False)
            for i, p in enumerate(prompts):
                eng.submit(Request(rid=i, prompt=p,
                                   max_new_tokens=budgets[i]))
            return {r.rid: r.tokens_out for r in eng.run()}

        assert run(None) == run(plan)
        print("PIPE-ENGINE-OK")
    """)


def test_serve_dryrun_prints_shardings():
    """launch/serve.py --dryrun: per-leaf shardings are printed (and
    nothing is served) for both plan modes."""
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = src
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.serve", "--arch",
         "smollm-135m", "--reduced", "--plan", "serve", "--mesh", "1,8",
         "--dryrun"], capture_output=True, text=True, env=env, timeout=300)
    assert out.returncode == 0, out.stdout + "\n" + out.stderr
    assert "mode=serve" in out.stdout and "paged arena" in out.stdout
    assert "scan/b0/mix/wq" in out.stdout and "'model'" in out.stdout
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.serve", "--arch",
         "smollm-135m", "--reduced", "--plan", "serve_pipeline", "--mesh",
         "2", "--dryrun"], capture_output=True, text=True, env=env,
        timeout=300)
    assert out.returncode == 0, out.stdout + "\n" + out.stderr
    assert "mode=serve_pipeline" in out.stdout
    assert "'stage'" in out.stdout
