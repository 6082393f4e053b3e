"""Fleet router: prefix-affinity dispatch over engine replicas.

Correctness bar (docs/fleet.md): the router only chooses *where* a
request runs — a 1-replica fleet's token streams are bit-identical to
the plain engine's; affinity routing concentrates each shared prefix on
one replica (more tree hits than the round-robin control); shedding and
stale-affinity fallback degrade politely (reason strings and cold
prefills, never errors).
"""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

import jax

from repro.configs import get_config
from repro.models.transformer import init_params, make_model
from repro.serving.engine import ContinuousBatchingEngine, Request
from repro.serving.replica import Replica, replica_device_groups
from repro.serving.router import (
    AffinityIndex, FleetConfig, FleetRouter, build_fleet,
)
from repro.serving.stream import (
    clone_requests, multi_prefix_requests, shared_prefix_requests,
)

ENGINE_KW = dict(max_batch=2, buckets=(16, 32, 64), num_pages=64)


@pytest.fixture(scope="module")
def setup():
    cfg = get_config("smollm-135m").reduced()
    model = make_model(cfg, remat=False)
    params = init_params(cfg, jax.random.PRNGKey(0))
    return cfg, model, params


# -- config + index units -----------------------------------------------------

def test_fleet_config_validates_route():
    with pytest.raises(ValueError, match="route"):
        FleetConfig(route="random")
    assert FleetConfig(shed_depth=3, shed_budget=1.5).shed_limit == 5
    assert FleetConfig(shed_depth=0).shed_limit == 0  # shedding disabled


def test_affinity_index_caps_match_at_len_minus_one():
    """Mirrors the radix tree's always-re-ingest-the-last-token rule: an
    exactly-block-aligned prompt matches one block short."""
    idx = AffinityIndex(block=4)
    toks = np.arange(12, dtype=np.int32)
    idx.insert(toks, replica=1)
    rep, hit = idx.lookup(toks)
    assert (rep, hit) == (1, 8)  # (12-1)//4 = 2 blocks, not 3
    rep, hit = idx.lookup(np.arange(13, dtype=np.int32))
    assert (rep, hit) == (1, 12)
    assert idx.lookup(np.arange(3, dtype=np.int32)) == (-1, 0)


def test_affinity_index_last_writer_wins():
    idx = AffinityIndex(block=4)
    toks = np.arange(8, dtype=np.int32)
    idx.insert(toks, replica=0)
    idx.insert(toks, replica=2)
    assert idx.lookup(np.arange(9, dtype=np.int32)) == (2, 8)


def test_replica_device_groups_partition_and_overflow():
    n = len(jax.devices())
    groups = replica_device_groups(n, 1)
    assert [d for g in groups for d in g] == jax.devices()
    with pytest.raises(ValueError, match="devices"):
        replica_device_groups(n + 1, 1)


# -- routing policies (no engine runs needed) ---------------------------------

def _req(rid, prompt_len, budget=4, rng=None):
    rng = rng or np.random.default_rng(rid)
    return Request(rid=rid,
                   prompt=rng.integers(0, 100, prompt_len).astype(np.int32),
                   max_new_tokens=budget)


def test_least_loaded_uses_projected_occupancy(setup):
    """Occupancy is token-steps (bucketed prompt + remaining budget), not
    request count: one long prompt outweighs several short ones."""
    _, model, params = setup
    fleet = build_fleet(model, params, 2,
                        config=FleetConfig(route="least-loaded"), **ENGINE_KW)
    fleet.submit(_req(0, prompt_len=60, budget=4))   # replica 0: 64+4 steps
    d1 = fleet.submit(_req(1, prompt_len=8, budget=4))
    assert d1.replica == 1
    d2 = fleet.submit(_req(2, prompt_len=8, budget=4))  # 1 holds 16+4 < 68
    assert d2.replica == 1
    assert fleet.replicas[0].projected_occupancy() == 68
    assert fleet.replicas[1].projected_occupancy() == 2 * (16 + 4)


def test_rebalance_overrides_overloaded_affinity_target(setup):
    """Deadline-aware balancing: an affinity hit is abandoned when the
    target's backlog exceeds least-loaded by > rebalance_margin."""
    _, model, params = setup
    fleet = build_fleet(model, params, 2,
                        config=FleetConfig(route="affinity",
                                           rebalance_margin=50), **ENGINE_KW)
    rng = np.random.default_rng(0)
    prefix = rng.integers(0, 100, 30).astype(np.int32)

    def with_prefix(rid, tail):
        return Request(rid=rid, prompt=np.concatenate(
            [prefix, rng.integers(0, 100, tail).astype(np.int32)]),
            max_new_tokens=4)

    assert fleet.submit(with_prefix(0, 2)).kind == "least-loaded"  # cold
    hot = fleet.decisions[0].replica
    d = fleet.submit(with_prefix(1, 3))
    assert d.kind == "affinity" and d.replica == hot
    assert d.expected_hit_tokens == 16  # (30-1)//16 blocks of the prefix
    # pile 2x(64+4) token-steps more onto the hot replica -> lag > margin
    fleet.replicas[hot].submit(_req(90, prompt_len=60))
    fleet.replicas[hot].submit(_req(91, prompt_len=60))
    d = fleet.submit(with_prefix(2, 4))
    assert d.kind == "rebalanced" and d.replica != hot
    assert d.expected_hit_tokens == 0  # the hit was given up, not claimed


def test_shed_only_when_every_replica_saturated(setup):
    _, model, params = setup
    fleet = build_fleet(model, params, 2,
                        config=FleetConfig(route="least-loaded",
                                           shed_depth=2), **ENGINE_KW)
    for rid in range(3):  # queues 2/1 -> replica 1 below limit, no shed
        assert fleet.submit(_req(rid, 8)).kind == "least-loaded"
    d = fleet.submit(_req(3, 8))  # queues 2/2 after: still routed (2/1 now)
    assert d.kind == "least-loaded"
    d = fleet.submit(_req(4, 8))  # every queue at limit 2 -> shed
    assert d.kind == "shed" and d.replica is None
    assert "saturated" in d.reason and "2 replicas" in d.reason
    assert fleet.shed[0][0].rid == 4
    done = fleet.run()  # shed requests never reach an engine
    assert sorted(r.rid for r in done) == [0, 1, 2, 3]
    assert fleet.stats()["shed"] == 1


# -- end-to-end: streams, hits, staleness -------------------------------------

def test_single_replica_fleet_matches_plain_engine(setup):
    cfg, model, params = setup
    rng = np.random.default_rng(0)
    stream = shared_prefix_requests(rng, 6, cfg.vocab_size, prefix_len=24,
                                    suffix_range=(2, 6), budgets=(3, 7))
    plain = ContinuousBatchingEngine(model, params, **ENGINE_KW)
    for r in clone_requests(stream):
        plain.submit(r)
    want = {r.rid: r.tokens_out for r in plain.run()}

    fleet = build_fleet(model, params, 1, **ENGINE_KW)
    for r in clone_requests(stream):
        assert fleet.submit(r).replica == 0
    got = {r.rid: r.tokens_out for r in fleet.run()}
    assert got == want  # bit-identical: the router is placement-only


def test_affinity_concentrates_prefixes_and_beats_round_robin(setup):
    cfg, model, params = setup
    rng = np.random.default_rng(1)
    stream = multi_prefix_requests(rng, 12, cfg.vocab_size, n_prefixes=2,
                                   prefix_len=24, suffix_range=(2, 6),
                                   budgets=(3, 7))
    hits = {}
    for route in ("affinity", "round-robin"):
        # high margin: this test isolates pure placement (the rebalance
        # override has its own test above)
        fleet = build_fleet(model, params, 2,
                            config=FleetConfig(route=route,
                                               rebalance_margin=10_000),
                            **ENGINE_KW)
        for r in clone_requests(stream):
            fleet.submit(r)
        done = fleet.run()
        assert len(done) == 12
        hits[route] = fleet.stats()["prefix_hits"]
    # affinity: one cold per prefix; round-robin: up to one cold per
    # (replica, prefix) pair on the same stream
    assert hits["affinity"] == 10
    assert hits["affinity"] > hits["round-robin"]


def test_stale_affinity_entry_falls_back_to_cold_prefill(setup):
    """The index records where a prefix was *sent*, not whether the
    replica still caches it: evict the tree behind the router's back and
    the re-routed request pays one cold prefill — same tokens, no error."""
    cfg, model, params = setup
    rng = np.random.default_rng(2)
    stream = shared_prefix_requests(rng, 4, cfg.vocab_size, prefix_len=24,
                                    suffix_range=(2, 6), budgets=4)
    fleet = build_fleet(model, params, 2, **ENGINE_KW)
    for r in clone_requests(stream):
        fleet.submit(r)
    first = {r.rid: r.tokens_out for r in fleet.run()}
    target = next(d.replica for d in fleet.decisions)
    hits0 = fleet.stats()["prefix_hits"]

    # drop every cached page on the target replica; the index still
    # points at it
    evicted = fleet.replicas[target].engine.kv.evict_cached(10 ** 6)
    assert evicted > 0
    again = clone_requests(stream)[:1]
    d = fleet.submit(again[0])
    assert d.kind == "affinity" and d.replica == target
    assert d.expected_hit_tokens > 0  # the index's (stale) promise
    done = fleet.run()
    assert done[0].tokens_out == first[done[0].rid]  # stream unchanged
    assert fleet.stats()["prefix_hits"] == hits0  # cold prefill, no hit


def test_replica_stats_deltas_and_router_aggregation(setup):
    cfg, model, params = setup
    rng = np.random.default_rng(3)
    stream = shared_prefix_requests(rng, 4, cfg.vocab_size, prefix_len=24,
                                    suffix_range=(2, 6), budgets=4)
    fleet = build_fleet(model, params, 2, **ENGINE_KW)
    for r in clone_requests(stream):
        fleet.submit(r)
    fleet.run()
    st = fleet.stats()
    assert st["submitted"] == 4 and st["shed"] == 0
    assert sum(p["routed"] for p in st["replicas"]) == 4
    assert sum(p["admitted"] for p in st["replicas"]) == 4
    assert st["by_kind"].get("affinity", 0) + \
        st["by_kind"].get("least-loaded", 0) == 4
    rep = st["replicas"][fleet.decisions[0].replica]
    assert rep["prefix_hit_rate"] == pytest.approx(
        rep["prefix_hits"] / rep["admitted"])
    # a fresh Replica wrapper sees only post-join deltas
    wrapped = Replica(9, fleet.replicas[0].engine)
    assert wrapped.stats()["admitted"] == 0


@pytest.mark.skipif(len(jax.devices()) < 2,
                    reason="plan-placed fleet needs >= 2 devices")
def test_fleet_on_disjoint_device_groups(setup):
    """Each replica's engine lives on its own device group and the fleet
    still matches the plain single-device engine bit-for-bit."""
    from repro.core.cluster_builder import build_plan
    from repro.serving.replica import make_group_mesh

    cfg, model, params = setup
    groups = replica_device_groups(2, 1)
    plans = [build_plan(cfg, make_group_mesh(g, (1, 1), ("data", "model")),
                        mode="serve") for g in groups]
    rng = np.random.default_rng(4)
    stream = shared_prefix_requests(rng, 4, cfg.vocab_size, prefix_len=24,
                                    suffix_range=(2, 6), budgets=4)
    plain = ContinuousBatchingEngine(model, params, **ENGINE_KW)
    for r in clone_requests(stream):
        plain.submit(r)
    want = {r.rid: r.tokens_out for r in plain.run()}
    fleet = build_fleet(model, params, 2, plans=plans, **ENGINE_KW)
    for r in clone_requests(stream):
        fleet.submit(r)
    got = {r.rid: r.tokens_out for r in fleet.run()}
    assert got == want


def test_fleet_without_plans_spreads_replicas_over_devices():
    """plans=None on a host with >= N devices: every replica's params and
    arena sit on a device of their own, and the streams still match one
    plain engine (forced 4-device host, so a subprocess)."""
    script = """
    import jax, numpy as np
    from repro.configs import get_config
    from repro.models.transformer import init_params, make_model
    from repro.serving.engine import ContinuousBatchingEngine
    from repro.serving.router import FleetConfig, build_fleet
    from repro.serving.stream import clone_requests, shared_prefix_requests
    kw = dict(max_batch=2, buckets=(16, 32, 64), num_pages=64)
    cfg = get_config("smollm-135m").reduced()
    model = make_model(cfg, remat=False)
    params = init_params(cfg, jax.random.PRNGKey(0))
    stream = shared_prefix_requests(np.random.default_rng(4), 8,
                                    cfg.vocab_size, prefix_len=24,
                                    suffix_range=(2, 6), budgets=4)
    fleet = build_fleet(model, params, 4,
                        config=FleetConfig(route="round-robin"), **kw)
    for r in clone_requests(stream):
        fleet.submit(r)
    got = {r.rid: r.tokens_out for r in fleet.run()}
    homes = []
    for rep in fleet.replicas:
        ex = rep.engine.executor
        devs = {d for leaf in jax.tree.leaves((ex.params,
                                               rep.engine._slot_caches))
                for d in leaf.devices()}
        assert len(devs) == 1, devs
        homes.append(devs.pop())
    assert len(set(homes)) == 4, homes
    plain = ContinuousBatchingEngine(model, params, **kw)
    for r in clone_requests(stream):
        plain.submit(r)
    assert got == {r.rid: r.tokens_out for r in plain.run()}
    print("OK", sorted(d.id for d in homes))
    """
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.path.join(os.path.dirname(__file__), "..",
                                       "src"))
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(script)],
                         capture_output=True, text=True, env=env,
                         timeout=600)
    assert out.returncode == 0, out.stdout + "\n" + out.stderr
    assert "OK [0, 1, 2, 3]" in out.stdout
