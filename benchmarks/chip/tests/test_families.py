"""Model families: the harness reaches the model only through
families/<model_type>.py, found by the configuration's model_type.

The pinned digests and counts were computed with the harness as it was
before the dense code moved into families/llama.py (weights.py,
reference.py and costs.py held it), so the family reproduces it bit for
bit: the same weights for a seed, the same reference and control logits,
the same work counts.

    JAX_PLATFORMS=cpu python -m pytest -q benchmarks/chip/tests
"""
from __future__ import annotations

import hashlib
import json
import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np  # noqa: E402
import pytest  # noqa: E402

HERE = Path(__file__).resolve().parent
CHIP = HERE.parent
sys.path[:0] = [str(CHIP), str(CHIP.parents[1] / "src")]

import bench  # noqa: E402

DATA = HERE / "data"
SEED = 123
# sha256 over the tree's leaves in flattening order: path, dtype and
# shape, then the bytes
TINY_WEIGHTS = "fed18864dc2c6e3c98d09129fa32a3db3329fe19289605d9dd6e4fd186ea8c69"
# sha256 of the float32 logits at positions 32..39 of 40 tokens
TINY_LOGITS = {
    "": "3a85413ae75d1d47ad95bb5771db8c680491cb1f1fc7bfab8701049c9a6d3753",
    "w8a8": "55a7f649dca8256d385e145d60a133e4bc905e69eac5bcad9dec48209334fc64",
    "fp8": "cd18ca1abea92cd5cf2755b66507d6af468b6588b76a55cdda57f6a2b772b40a",
}
# token_flops(s, 5, True), token_flops(s, 0, False), prefill_flops(s, 4),
# prefill_flops(s, 1000), head_flops, attn_flops_per_key, kv_bytes_per_key
COUNTS = {
    "tiny": [182784, 147456, 627712, 403744768, 32768, 512, 256],
    "smollm-135m": [269305344, 212336640, 906660864, 246987823104,
                    56623104, 69120, 23040],
    "phi3-medium-14b-stage": [7145103360, 6815744000, 27593359360,
                              6918574735360, 328335360, 204800, 51200],
}
CONFIGS = {"tiny": DATA / "tiny.json",
           "smollm-135m": CHIP / "configs" / "smollm-135m.json",
           "phi3-medium-14b-stage":
               CHIP / "configs" / "phi3-medium-14b-stage.json"}


def _family(path):
    return bench.load_family(json.loads(Path(path).read_text())["model_type"])


def _tiny():
    f = _family(CONFIGS["tiny"])
    return f, f.shapes(CONFIGS["tiny"], "tiny")


def test_weights_are_the_parents_bit_for_bit():
    import jax
    f, s = _tiny()
    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_flatten_with_path(
            f.served_params(s, SEED))[0]:
        a = np.asarray(leaf)
        h.update(jax.tree_util.keystr(path).encode())
        h.update(f"{a.dtype}{a.shape}".encode())
        h.update(a.tobytes())
    assert h.hexdigest() == TINY_WEIGHTS


@pytest.mark.parametrize("quant", ["", "w8a8", "fp8"],
                         ids=["float32", "w8a8", "fp8"])
def test_reference_logits_are_the_parents_bit_for_bit(quant):
    f, s = _tiny()
    seq = np.random.default_rng(SEED).integers(0, s.vocab, 40).astype(
        np.int32)
    out = f.logits(s, SEED, [seq], [np.arange(32, 40)], quant=quant)[0]
    assert out.dtype == np.float32 and out.shape == (8, s.vocab)
    assert hashlib.sha256(out.tobytes()).hexdigest() == TINY_LOGITS[quant]


@pytest.mark.parametrize("name", sorted(COUNTS))
def test_work_counts_are_the_parents(name):
    f = _family(CONFIGS[name])
    s = f.shapes(CONFIGS[name], name)
    got = [f.token_flops(s, 5, True), f.token_flops(s, 0, False),
           f.prefill_flops(s, 4), f.prefill_flops(s, 1000), f.head_flops(s),
           f.attn_flops_per_key(s), f.kv_bytes_per_key(s)]
    assert got == COUNTS[name]
    assert all(type(x) is int for x in got)


def _spec(config_file: Path) -> dict:
    spec = json.loads((CHIP.parents[1] / "BENCHMARK.json").read_text())
    for m in spec["end_to_end"] + spec["per_layer"]:
        m.pop("workloads", None)
    spec["configs"] = [{"name": "tiny", "file": str(config_file)}]
    spec["workloads"] = [{"name": "tiny.sessions", "config": "tiny",
                          "traffic": "tiny-sessions", "chips": 1}]
    return spec


def _config(tmp_path: Path, model_type: str) -> Path:
    c = json.loads(CONFIGS["tiny"].read_text())
    c["model_type"] = model_type
    path = tmp_path / f"tiny-{model_type}.json"
    path.write_text(json.dumps(c))
    return path


def _run(spec, **kw):
    return bench.run_cell(spec, "tiny.sessions", 2 ** 31 + 11, 2.0, False,
                          on_chip=False, limits={"max_logit_gap": 0.08},
                          traffic_dir=DATA, log=lambda m: None, **kw)


def test_unknown_model_type_fails_naming_the_known_families(tmp_path):
    spec = _spec(_config(tmp_path, "no_such_family"))
    with pytest.raises(SystemExit) as e:
        _run(spec)
    msg = str(e.value)
    assert "'no_such_family'" in msg
    assert "known families: ['llama', 'phi3']" in msg


PROBE_FAMILY = '''"""llama's dense decoder, counting what the harness asks of it."""
from families import llama as _base
from families.llama import *  # noqa: F401,F403

CALLS = []


def served_params(s, seed):
    CALLS.append("served_params")
    return _base.served_params(s, seed)


def logits(s, seed, seqs, positions, quant=""):
    CALLS.append("logits")
    return _base.logits(s, seed, seqs, positions, quant)
'''


def test_a_family_added_as_a_new_file_is_served(tmp_path):
    """A family module in a directory of its own, named by a config's
    model_type, is loaded and served through run_cell; no file of the
    harness names it."""
    where = tmp_path / "families"
    where.mkdir()
    (where / "tiny_probe.py").write_text(PROBE_FAMILY)
    out = _run(_spec(_config(tmp_path, "tiny_probe")), families_dir=where)
    assert out["correct"], out["checks"]
    assert sys.modules["family_tiny_probe"].CALLS == ["served_params",
                                                      "logits"]
    with pytest.raises(SystemExit, match=r"known families: \['tiny_probe'\]"):
        bench.load_family("llama", where)
