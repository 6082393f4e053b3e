"""Spans and phase counters inside the serving loop (serving/tracing.py).

The engine names each part of its loop with a profiler annotation and
adds each part's host wall time to a monotone counter in
`engine.stats`.  These tests check, on a tiny engine on the CPU, that a
served run emits every span the benchmark reads plus the engine's own,
that the counters only grow, nest (`prefill_s` inside `admit_s`) and add
up to no more than `loop_s`, that they stand when `run()` ends by an
exception, that the collector's work is counted, that no device sync was
added, that the paged kernel's page-walk counters match a hand count,
that the benchmark's three readers of the counters compute what they
say, and that every jitted program has a name of its own.
"""
import ast
import gc
import importlib.util
from pathlib import Path

import numpy as np
import pytest

import jax

from repro.configs import get_config
from repro.models.transformer import init_params, make_model
from repro.serving import tracing
from repro.serving.engine import ContinuousBatchingEngine, Request

ROOT = Path(__file__).resolve().parents[1]
CHIP = ROOT / "benchmarks" / "chip"
EXECUTOR = ROOT / "src" / "repro" / "serving" / "executor.py"
# the phases that together make up the serving loop
PARTS = ("admit_s", "decode_s", "reconcile_s", "idle_s")


@pytest.fixture(scope="module")
def tiny():
    cfg = get_config("smollm-135m").reduced()
    model = make_model(cfg, remat=False)
    return cfg, model, init_params(cfg, jax.random.PRNGKey(0))


def _engine(tiny, **kw):
    _, model, params = tiny
    return ContinuousBatchingEngine(model, params, max_batch=2,
                                    buckets=(16, 48), max_decode_len=24,
                                    **kw)


def _requests(cfg, t_arrival=0.0):
    """Five turns behind one 35-token prompt: one cold admission, four
    prefix hits (two full 16-token pages), budgets 5 to 13."""
    rng = np.random.default_rng(5)
    shared = rng.integers(0, cfg.vocab_size, 35).astype(np.int32)
    out = []
    for i in range(5):
        tail = np.random.default_rng(100 + i).integers(
            0, cfg.vocab_size, 4 + i).astype(np.int32)
        out.append(Request(rid=i, prompt=np.concatenate([shared, tail]),
                           max_new_tokens=5 + 2 * i, t_arrival=t_arrival))
    return out


def _serve(eng, reqs):
    for r in reqs:
        eng.submit(r)
    return eng.run()


def _counters(eng):
    return {k: eng.stats[k] for k in tracing.PHASES + ("gc_collections",)}


def _chip_spans():
    """benchmarks/chip/system.py's SPANS, read without importing it."""
    tree = ast.parse((CHIP / "system.py").read_text())
    for node in tree.body:
        if isinstance(node, ast.Assign) and any(
                getattr(t, "id", None) == "SPANS" for t in node.targets):
            return ast.literal_eval(node.value)
    raise AssertionError("system.py has no SPANS")


class _Recorder:
    """Stands in for jax.profiler.TraceAnnotation: records each span's
    name and metadata as it is entered."""
    seen = []

    def __init__(self, name, **meta):
        self.name, self.meta = name, meta

    def __enter__(self):
        _Recorder.seen.append((self.name, self.meta))

    def __exit__(self, *exc):
        return None


def test_served_run_emits_every_span(tiny, monkeypatch):
    cfg = tiny[0]
    monkeypatch.setattr(tracing, "_annotation", _Recorder)
    _Recorder.seen = []
    # the requests arrive after the loop starts: an idle wait first
    done = _serve(_engine(tiny), _requests(cfg, t_arrival=0.05))
    assert len(done) == 5
    names = {n for n, _ in _Recorder.seen}
    assert set(_chip_spans()) <= names
    assert {"block_sync", "cold_admission", "loop_control"} <= names
    assert names == set(tracing.SPANS)
    # admission spans carry the request's id
    rids = {n: {m.get("rid") for k, m in _Recorder.seen if k == n}
            for n in ("cold_admission", "admit_cold", "admit_hit",
                      "admit_lane")}
    assert rids["cold_admission"] == rids["admit_cold"] == {0}
    assert rids["admit_hit"] == {1, 2, 3, 4}
    assert rids["admit_lane"] == {0, 1, 2, 3, 4}


def test_phase_counters_grow_nest_and_cover_the_loop(tiny):
    cfg = tiny[0]
    eng = _engine(tiny)
    seen = [_counters(eng)]
    for _ in range(2):
        _serve(eng, _requests(cfg))
        seen.append(_counters(eng))
    for before, after in zip(seen, seen[1:]):
        for k in before:
            assert after[k] >= before[k] >= 0, k
        d = {k: after[k] - before[k] for k in before}
        assert d["loop_s"] > 0 and d["decode_s"] > 0 and d["admit_s"] > 0
        assert d["prefill_s"] <= d["admit_s"]
        assert d["sync_s"] <= d["loop_s"]
        assert sum(d[k] for k in PARTS) <= d["loop_s"]
    # the second pass is all prefix hits: no cold admission, no prefill
    assert seen[2]["prefill_s"] == seen[1]["prefill_s"]


def test_counters_stand_when_run_raises(tiny):
    cfg = tiny[0]
    eng = _engine(tiny)
    reconcile, calls = eng.sched.reconcile, []

    def fail_on_third(*a, **k):
        calls.append(1)
        if len(calls) == 3:
            raise RuntimeError("stop")
        return reconcile(*a, **k)

    eng.sched.reconcile = fail_on_third
    with pytest.raises(RuntimeError, match="stop"):
        _serve(eng, _requests(cfg))
    c = _counters(eng)
    assert eng.stats["decode_dispatches"] == 2
    assert c["loop_s"] > 0 and c["decode_s"] > 0 and c["reconcile_s"] > 0
    assert c["prefill_s"] <= c["admit_s"]
    assert sum(c[k] for k in PARTS) <= c["loop_s"]


def test_gc_collections_count_a_forced_collection(tiny):
    s0, n0 = tracing.gc_totals()
    gc.collect()
    s1, n1 = tracing.gc_totals()
    assert n1 >= n0 + 1 and s1 > s0
    cfg = tiny[0]
    eng = _engine(tiny)
    idle_wait = eng.sched.idle_wait

    def collect_then_wait(*a, **k):
        gc.collect()
        return idle_wait(*a, **k)

    eng.sched.idle_wait = collect_then_wait
    base = _counters(eng)
    _serve(eng, _requests(cfg, t_arrival=0.05))
    assert eng.stats["gc_collections"] - base["gc_collections"] >= 1
    assert eng.stats["gc_s"] > base["gc_s"]
    assert eng.stats["idle_s"] > 0


def test_device_syncs_unchanged(tiny):
    """One blocking read per decode dispatch and one per cold first
    token: 11 on this request set, as before the spans went in."""
    eng = _engine(tiny)
    _serve(eng, _requests(tiny[0]))
    assert eng.stats["decode_dispatches"] == 10
    assert eng.stats["prefills"] == 1
    assert eng.stats["device_syncs"] == 11


def test_page_walk_counters_by_hand(tiny):
    """decode_pages_walked is, over every step a lane ran active, the
    live pages min(MAXP, pos // ps + 1) of its query; decode_pages_grid
    is steps x max_batch x MAXP.  By hand over the two-lane schedule: a
    cold turn runs from its prompt's end L, a hit turn from its hit
    length h through its suffix, and both to L + budget - 2."""
    cfg = tiny[0]
    eng = _engine(tiny)
    reqs = _requests(cfg)
    _serve(eng, reqs)
    ps, maxp = eng.page_size, eng.max_pages
    assert (ps, maxp) == (16, 5)
    assert eng.stats["prefix_hits"] == 4  # every later turn hits 2 pages
    walked = 0
    for r in reqs:
        start = len(r.prompt) if r.rid == 0 else 32
        end = len(r.prompt) + r.max_new_tokens - 2
        walked += sum(min(maxp, p // ps + 1) for p in range(start, end + 1))
    # 12 + 42 + 52 + 64 + 76: 3 pages live below position 48, 4 from it
    assert eng.stats["decode_pages_walked"] == walked == 246
    assert eng.stats["decode_pages_grid"] == (
        eng.stats["decode_steps"] * 2 * maxp)


def _reader(name):
    spec = importlib.util.spec_from_file_location(
        f"metric_{name}", CHIP / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _run(**stats):
    from types import SimpleNamespace
    return SimpleNamespace(stats=stats)


@pytest.mark.parametrize("name,stats,want", [
    ("prefill_share", dict(loop_s=80.0, prefill_s=2.0), 2.5),
    ("prefill_share", dict(loop_s=80.0, prefill_s=0.0), 0.0),
    ("decode_step_ms", dict(decode_s=69.0, decode_steps=1000), 69.0),
    ("host_share", dict(loop_s=80.0, sync_s=70.0, idle_s=8.0), 2.5),
])
def test_counter_readers_by_hand(name, stats, want):
    assert _reader(name)(_run(**stats)) == pytest.approx(want)


@pytest.mark.parametrize("name", ["prefill_share", "decode_step_ms",
                                  "host_share"])
@pytest.mark.parametrize("stats", [
    {}, dict(loop_s=0.0, prefill_s=0.0, sync_s=0.0, idle_s=0.0,
             decode_s=0.0, decode_steps=0),
    # the counts the benchmark has without this module's counters
    dict(decode_steps=1000, device_syncs=1100, prefills=40)])
def test_counter_readers_give_none_without_counters(name, stats):
    assert _reader(name)(_run(**stats)) is None


def _jitted_names():
    """The function handed to each jax.jit(...) in executor.py."""
    tree = ast.parse(EXECUTOR.read_text())
    return [c.args[0].id for c in ast.walk(tree)
            if isinstance(c, ast.Call) and isinstance(c.func, ast.Attribute)
            and c.func.attr == "jit" and getattr(c.func.value, "id", "")
            == "jax" and c.args and isinstance(c.args[0], ast.Name)]


def test_every_program_has_its_own_name(tiny):
    names = _jitted_names()
    assert len(names) == 18
    assert len(set(names)) == len(names), names
    assert "fn" not in names
    # the paged decode alone holds "pfn": the device trace finds it so
    assert [n for n in names if "pfn" in n] == ["pfn"]
    # and the compiled module carries the name
    eng = _engine(tiny)
    _serve(eng, _requests(tiny[0]))
    ex = eng.executor
    st = ex.fresh_state(eng._slot_caches, True)
    with ex._ctx():
        text = ex.decode_fn(1, True).lower(
            ex.params, st["caches"], st["cur"], st["active"], st["eos"],
            st["budget"], st["forced"], st["flen"], st["fptr"]).as_text()
    assert "@jit_pfn" in text
    served = {f.__name__ for d in (ex._jit_prefill, ex._jit_admit_cold)
              for f in d.values()}
    served |= {ex._jit_admit_hit.__name__, ex._jit_admit_lane_paged.__name__,
               ex._jit_park.__name__}
    assert served == {"prefill", "admit_cold", "admit_hit",
                      "admit_lane_paged", "park_lane"}
