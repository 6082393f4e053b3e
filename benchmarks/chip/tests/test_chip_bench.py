"""The benchmark's own yardstick, on the CPU: trace reduction, traffic,
weights, the reference against the served model, work counts, peaks.

    JAX_PLATFORMS=cpu python -m pytest -q benchmarks/chip/tests
"""
from __future__ import annotations

import json
import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np  # noqa: E402
import pytest  # noqa: E402

HERE = Path(__file__).resolve().parent
CHIP = HERE.parent
ROOT = CHIP.parents[1]
sys.path[:0] = [str(CHIP), str(ROOT / "src")]

import bench  # noqa: E402
import costs  # noqa: E402
import devtrace  # noqa: E402
import traffic as T  # noqa: E402
import weights as W  # noqa: E402

DATA = HERE / "data"
LLAMA = bench.load_family("llama")
TINY = LLAMA.shapes(DATA / "tiny.json", "tiny")


# -- trace reduction ---------------------------------------------------------

def test_reduce_union_gaps_and_op_time():
    rec = {"device": [["a", 0, 10], ["b", 5, 10], ["k", 30, 5],
                      ["k", 50, 10], ["a", 58, 4]],
           "host": [["decode_dispatch", 0, 40], ["reconcile", 14, 20],
                    ["admit_hit", 35, 20]]}
    red = devtrace.reduce(rec)
    assert red["busy_s"] == pytest.approx((15 + 5 + 12) * 1e-9)
    assert red["op_s"]["k"] == pytest.approx(15e-9)
    assert devtrace.op_seconds(red, r"^k$") == pytest.approx(15e-9)
    # gaps: (15, 30) inside reconcile, (35, 50) inside admit_hit
    assert red["gaps"] == [["reconcile", pytest.approx(15e-9)],
                           ["admit_hit", pytest.approx(15e-9)]]
    assert red["top_ops"][0][0] == "k"  # 15 ns against 14 and 10


def test_reduce_recorded_trace():
    """A slice of a traced run on a v5e chip (smollm-135m.sessions)."""
    path = DATA / "trace_slice.json"
    if not path.exists():
        pytest.skip("no recorded trace slice")
    rec = json.loads(path.read_text())["slice"]
    red = devtrace.reduce(rec)
    span = max(s + d for _, s, d in rec["device"]) - min(
        s for _, s, d in rec["device"])
    assert 0 < red["busy_s"] <= red["window_s"] == pytest.approx(span * 1e-9)
    # the loop ops hold the others: they are busy time, not op time
    assert not any(k.endswith(" while") for k in red["op_s"])
    assert red["top_ops"][0][0].startswith("paged_flash_decode")
    kernel = devtrace.op_seconds(red, r"^paged_flash_decode")
    assert 0.3 * red["busy_s"] < kernel < red["busy_s"]
    for name, secs in red["gaps"]:
        assert secs >= 0 and isinstance(name, str)


# -- traffic -----------------------------------------------------------------

SESSIONS = T.load(CHIP / "traffic" / "sessions.json")
BATCH = T.load(CHIP / "traffic" / "batch.json")
LONG_DECODE = T.load(CHIP / "traffic" / "long-decode.json")
TRAFFIC = pytest.mark.parametrize("tr", [SESSIONS, BATCH, LONG_DECODE],
                                  ids=["sessions", "batch", "long-decode"])


@TRAFFIC
def test_traffic_deterministic_and_bounded(tr):
    a = T.generate(tr, 1000, 2 ** 31 + 77, 30.0)
    b = T.generate(tr, 1000, 2 ** 31 + 77, 30.0)
    assert [(x.t_arrival, x.budget) for x in a] == \
        [(x.t_arrival, x.budget) for x in b]
    assert all((x.prompt == y.prompt).all() for x, y in zip(a, b))
    longest, longest_out = T.bounds(tr)
    shortest = T.prompt_range(tr)[0]
    for x in a:
        assert 0 <= x.t_arrival < 30.0
        assert shortest <= len(x.prompt) <= longest
        assert tr["output"]["min"] <= x.budget <= longest_out
        assert len(x.prompt) + x.budget <= tr["max_context"]
    assert [x.t_arrival for x in a] == sorted(x.t_arrival for x in a)


@TRAFFIC
def test_seeds_carry_the_same_work(tr):
    """Every seed: the same requests, sizes and arrival times, in the same
    order; other tokens."""
    def work(seed):
        xs = T.generate(tr, 1000, seed, 60.0)
        return ([(len(x.prompt), x.budget) for x in xs],
                [x.t_arrival for x in xs], xs)
    (s1, t1, a), (s2, t2, b) = work(1), work(2 ** 33 + 5)
    assert s1 == s2 and t1 == t2
    assert not all((x.prompt.shape == y.prompt.shape and
                    (x.prompt == y.prompt).all()) for x, y in zip(a, b))


def test_sessions_share_prefix_and_grow():
    turns = T.generate(SESSIONS, 1000, 5, 60.0)
    sys_prompt = turns[0].prompt[:SESSIONS["shared_prefix"]]
    by_session = {}
    for x in turns:
        assert (x.prompt[:len(sys_prompt)] == sys_prompt).all()
        by_session.setdefault(x.session, []).append(x)
    grew = 0
    for xs in by_session.values():
        xs.sort(key=lambda x: x.turn)
        for p, q in zip(xs, xs[1:]):
            if q.turn == p.turn + 1:
                # the next prompt holds this prompt, then the answer
                assert (q.prompt[:len(p.prompt)] == p.prompt).all()
                assert len(q.prompt) >= len(p.prompt) + p.budget + 8
                assert q.t_arrival - p.t_arrival >= SESSIONS["think_s"][0]
                grew += 1
    assert grew > 5


# -- weights and the reference ----------------------------------------------

def test_served_params_match_layer_draws_and_program_layout():
    import jax

    import system as S
    params = LLAMA.served_params(TINY, 123)
    S.check_layout(LLAMA.model_config(TINY), params)
    lw = LLAMA.layer(TINY, W.seed_key(123), 1)
    mix = params["scan"]["b0"]["mix"]
    assert (np.asarray(mix["wq"][1]) == np.asarray(lw["wq"])).all()
    assert (np.asarray(params["scan"]["b0"]["ffn"]["wo"][1])
            == np.asarray(lw["wd"])).all()
    emb = W.embedding(W.seed_key(123), TINY.vocab, TINY.d, TINY.std,
                      TINY.tied)
    assert (np.asarray(params["embed"]["head"]) == np.asarray(emb["head"])).all()
    assert jax.tree_util.tree_leaves(params)[0].dtype.name in ("bfloat16",
                                                               "float32")


def test_reference_matches_the_served_model():
    """The reference against the program's own forward pass at a tiny
    size: with the served weights in float32 the two are the same
    arithmetic; in bfloat16, as served, they agree on most argmaxes."""
    import jax
    import jax.numpy as jnp

    from repro.models.transformer import make_model

    seed = 7
    params = LLAMA.served_params(TINY, seed)
    model = make_model(LLAMA.model_config(TINY), remat=False)
    seq = T.seed_rng(seed).integers(0, TINY.vocab, 40).astype(np.int32)
    tok = jnp.asarray(seq)[None]
    p32 = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    with jax.default_matmul_precision("highest"):
        prog32 = np.asarray(model.forward_logits(p32, tokens=tok))[0]
    prog = np.asarray(model.forward_logits(params, tokens=tok))[0]
    pos = np.arange(40)
    ref = LLAMA.logits(TINY, seed, [seq], [pos])[0]
    scale = np.abs(ref).max()
    assert np.abs(prog32 - ref).max() < 1e-4 * scale
    assert np.abs(prog - ref).max() < 0.1 * scale
    assert (prog.argmax(-1) == ref.argmax(-1)).mean() >= 0.9
    for q in LLAMA.CONTROLS:
        ctl = LLAMA.logits(TINY, seed, [seq], [pos], quant=q)[0]
        assert np.abs(ctl - ref).max() > 1e-3 * scale


# -- work counts and peaks ---------------------------------------------------

def test_flop_and_byte_counts_by_hand():
    f = LLAMA
    s = f.shapes(CHIP / "configs" / "smollm-135m.json", "smollm-135m")
    # q 576x576, k and v 576x192, o 576x576, three 576x1536
    assert f.layer_matmul_params(s) == (
        576 * 576 * 2 + 576 * 192 * 2 + 3 * 576 * 1536)
    assert f.kv_bytes_per_key(s) == 2 * 3 * 64 * 2 * 30
    assert f.attn_flops_per_key(s) == 4 * 9 * 64 * 30
    assert costs.decode_keys(10, 3) == 11 + 12 + 13
    per_token = 2 * 30 * f.layer_matmul_params(s)
    assert f.prefill_flops(s, 4) == (
        4 * per_token + 4 * 9 * 64 * 30 * (1 + 2 + 3 + 4) + 2 * 576 * 49152)
    assert f.token_flops(s, 5, True) == (
        per_token + 4 * 9 * 64 * 30 * 5 + 2 * 576 * 49152)
    peak = costs.peaks("TPU v5 lite")
    assert costs.roofline_share(0, 819e9, 2.0, peak) == pytest.approx(50.0)
    assert costs.roofline_share(197e12, 0, 1.0, peak) == pytest.approx(100.0)


def test_token_time_readers():
    """TTFT, TPOT and tokens/s from hand-made token times: a block's time
    since the request's previous block falls on the dispatch's steps, the
    most tokens any request got in it."""
    from types import SimpleNamespace as NS

    a = NS(t_enqueue=10.0, t_tokens=[10.5, 10.5, 10.9, 10.9, 11.0],
           blocks=[1, 1, 2, 2, 3])  # first block 2 tokens, then 2, then 1
    c = NS(t_enqueue=10.0, t_tokens=[10.6, 11.0, 11.0],
           blocks=[2, 3, 3])  # block 3 ran 2 steps; `a` ended in its first
    b = NS(t_enqueue=10.2, t_tokens=[], blocks=[])  # never served
    run = NS(requests=[a, c], t0=10.0, seconds=1.0)
    tpot = bench.reader("tpot_p95_ms")(run)
    assert tpot == pytest.approx(np.percentile([200, 200, 50, 200, 200], 95))
    assert bench.reader("ttft_p95_ms")(run) == pytest.approx(
        np.percentile([500, 600], 95))
    assert bench.reader("output_tok_s")(run) == pytest.approx(5.0)
    run.requests.append(b)
    assert bench.reader("ttft_p95_ms")(run) == np.inf


def test_peaks_refuse_an_unknown_device():
    with pytest.raises(KeyError):
        costs.peaks("TPU v9 imaginary")


@pytest.mark.parametrize("tr,want", [
    (BATCH, [16, 32, 64, 128, 256, 512, 1024, 2048]),
    (LONG_DECODE, [16, 32, 64, 128])], ids=["batch", "long-decode"])
def test_warm_pairs_cover_every_cold_admission(tr, want):
    """Every request of the schedule, cold as it arrives or re-admitted
    after preemption with part of its answer (as far as a prefill bucket
    holds it), finds its program warm."""
    import system as S
    longest, _ = T.bounds(tr)
    buckets = bench.buckets_for(longest)
    sched = T.generate(tr, 1000, 3, 120.0)
    pairs = set(S.warm_pairs(buckets, [(len(x.prompt), x.budget)
                                       for x in sched]))
    for x in sched:
        for done in (0, x.budget // 2, x.budget - 1):
            if len(x.prompt) + done > buckets[-1]:
                continue
            b = S.bucket_of(len(x.prompt) + done, buckets)
            w = min(S.pages(b), S.pages(len(x.prompt) + x.budget))
            assert (b, w) in pairs
    assert buckets == want
