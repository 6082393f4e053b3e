"""The deepseek_v3 family (Moonlight-16B-A3B, one chip's expert share) and
the expert_gmm_roofline reader, on the CPU.

    JAX_PLATFORMS=cpu python -m pytest -q benchmarks/chip/tests
"""
from __future__ import annotations

import json
import os
import sys
from pathlib import Path
from types import SimpleNamespace

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import pytest  # noqa: E402

HERE = Path(__file__).resolve().parent
CHIP = HERE.parent
sys.path[:0] = [str(CHIP), str(CHIP.parents[1] / "src")]

import bench  # noqa: E402
import costs  # noqa: E402

DATA = HERE / "data"
F = bench.load_family("deepseek_v3")
TINY = F.shapes(DATA / "tiny-deepseek.json", "tiny")
MOON = F.shapes(CHIP / "configs" / "moonlight-16b-a3b-ep8.json",
                "moonlight-16b-a3b-ep8")


def _sig(tree):
    import jax
    return jax.tree_util.tree_map(lambda a: (a.shape, str(a.dtype)), tree)


@pytest.mark.parametrize("s", [TINY, MOON], ids=["tiny", "moonlight"])
def test_weights_fill_the_programs_tree(s):
    """served_params has init_params' layout for the family's config: 8
    held experts a MoE layer, the dense layer under "lead"."""
    import jax
    from repro.models.transformer import init_params
    want = jax.eval_shape(lambda: init_params(F.model_config(s),
                                              jax.random.PRNGKey(0)))
    got = jax.eval_shape(lambda: F.served_params(s, 7))
    assert _sig(got) == _sig(want)
    if s is MOON:
        ffn = got["scan"]["b0"]["ffn"]
        assert ffn["wi"].shape == (26, 8, 2048, 1408)
        assert ffn["router"].shape == (26, 2048, 64)
        assert got["lead"]["0"]["ffn"]["wi"].shape == (2048, 11264)


def test_moonlight_work_counts():
    s = MOON
    assert (s.layers, s.dense, s.held, s.experts, s.top_k) == (27, 1, 8, 64,
                                                                 6)
    assert F.attn_flops_per_key(s) == 2 * 16 * (576 + 512) * 27
    assert F.kv_bytes_per_key(s) == 1152 * 27
    assert F.held_pairs_per_token(s) == 6 * 8 / 64
    assert F.expert_pair_flops(s) == 2 * 3 * 2048 * 1408
    assert F.expert_bytes(s) == 3 * 2048 * 1408 * 2
    attn = 2048 * 3072 + 2048 * 576 + 512 * 4096 + 2048 * 2048
    assert attn == 13_762_560
    moe = 2 * (attn + 2048 * 64 + 3 * 2048 * 2816) + 0.75 * 6 * 2048 * 1408
    dense = 2 * (attn + 3 * 2048 * 11264)
    assert F.token_flops(s, 0, False) == round(dense + 26 * moe)
    assert F.token_flops(s, 10, True) == (F.token_flops(s, 0, False)
                                          + 10 * F.attn_flops_per_key(s)
                                          + 2 * 2048 * 163840)
    assert all(type(x) is int for x in (F.token_flops(s, 3, True),
                                        F.prefill_flops(s, 100)))


def _spec():
    spec = json.loads((CHIP.parents[1] / "BENCHMARK.json").read_text())
    for m in spec["end_to_end"] + spec["per_layer"]:
        m.pop("workloads", None)
    spec["configs"] = [{"name": "tiny",
                        "file": str(DATA / "tiny-deepseek.json")}]
    spec["workloads"] = [{"name": "tiny.sessions", "config": "tiny",
                          "traffic": "tiny-sessions", "chips": 1}]
    return spec


def test_tiny_model_served_and_checked_against_the_reference():
    """The whole harness on the CPU: paged latent arena, radix hits on the
    shared prefix, the expert share; the served tokens agree with the
    float32 reference and both controls do not."""
    out = bench.run_cell(_spec(), "tiny.sessions", 2 ** 31 + 11, 2.0, False,
                         on_chip=False, limits={"max_logit_gap": 0.3},
                         traffic_dir=DATA, log=lambda m: None, control=True)
    assert out["correct"], out["checks"]
    st = out["_extra"]["stats"]
    assert st["prefix_hits"] > 0
    assert 0 < st["moe_pairs_held"] < st["moe_pairs_routed"]
    assert 0 < st["moe_experts_hit"]
    for q in F.CONTROLS:
        assert out["_extra"]["control"][q]["max_logit_gap"] > 0.3, q


def _reader_run(op_s):
    """Two lanes, three decode dispatches of 4 steps (stamps 1, 2, 3)
    after their first tokens (stamps 0.1, 0.2); the trace holds the last
    two."""
    reqs = [SimpleNamespace(prompt=[0] * 10, blocks=[0.1] + [1] * 4
                            + [2] * 4 + [3] * 4),
            SimpleNamespace(prompt=[0] * 30, blocks=[0.2] + [1] * 4
                            + [2] * 4 + [3] * 2)]
    keys = (costs.decode_keys(10 + 5 - 1, 4) + costs.decode_keys(10 + 9 - 1, 4)
            + costs.decode_keys(30 + 5 - 1, 4)
            + costs.decode_keys(30 + 9 - 1, 2))
    stats = {"moe_pairs_routed": 100, "moe_pairs_held": 25,
             "moe_experts_hit": 60, "decode_steps": 12}
    s = SimpleNamespace(top_k=6, layers=3, dense=1, d=64, moe_ff=32, held=2)
    return SimpleNamespace(
        trace={"keys": keys, "op_s": op_s}, peak={"bf16_flops_per_s": 1e12,
                                                  "hbm_bytes_per_s": 1e9},
        stats=stats, family=F, shapes=s, max_batch=2, requests=reqs,
        t0=0.0, seconds=4.0)


def test_expert_gmm_roofline_counts_the_traced_decode_steps():
    read = bench.reader("expert_gmm_roofline")
    run = _reader_run({"gmm.1 bf16[16,32] custom-call": 2e-6,
                       "gmm.2 bf16[16,64] custom-call": 1e-6,
                       "gmm.7 bf16[96,32] custom-call": 5.0,
                       "fusion.3 bf16[16,64] fusion": 9.0,
                       "dynamic-slice_fusion.6 bf16[2,64,32] fusion": 4e-6,
                       "dynamic-slice_fusion.8 bf16[2,32,64] fusion": 2e-6,
                       "copy.2 bf16[3,64,32] copy": 7.0})
    # the last two dispatches: 8 steps, 14 lane steps; 12 rows padded to
    # 16; the time is the decode-row calls' and their weights' staging
    steps, lane_steps = 8, 14
    pairs = lane_steps * 6 * 25 / 100 * 2
    nbytes = (steps * 60 / 12 * F.expert_bytes(run.shapes)
              + pairs * 2 * 3 * (64 + 32))
    want = costs.roofline_share(pairs * F.expert_pair_flops(run.shapes),
                                nbytes, 9e-6, run.peak)
    assert read(run) == pytest.approx(want)


def test_expert_gmm_roofline_reads_nothing_without_a_share():
    read = bench.reader("expert_gmm_roofline")
    run = _reader_run({"gmm.1 bf16[16,32] custom-call": 2e-6})
    run.stats = {"decode_steps": 12}
    assert read(run) is None
    run = _reader_run({"paged_flash_decode.1 f32[2] custom-call": 1.0})
    assert read(run) is None
    # the calls without the staging of their weights: no reading
    run = _reader_run({"gmm.1 bf16[16,32] custom-call": 2e-6})
    assert read(run) is None
