"""`correct` has to come out false when the timed path is wrong.

Each test drives the whole of a run except the look for a chip (a tiny
model on the CPU, the harness's own traffic mechanism, the engine as the
benchmark builds it), with the timed path broken underneath, and sees
`correct` false.  The faults a served one-chip cell can have:

- a decode step that returns its state unchanged;
- a token altered where it is produced;
- the control: the reference itself in float8 in the program's place,
  read at the positions the program served, whose numbers must exceed
  a limit the program passes.

(Half of a batch left out with the mean taken over the rest is a
training fault, and the exchange between chips needs more than one
chip: no cell here can have either.)

    JAX_PLATFORMS=cpu python -m pytest -q benchmarks/chip/tests
"""
from __future__ import annotations

import json
import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import pytest  # noqa: E402

HERE = Path(__file__).resolve().parent
CHIP = HERE.parent
sys.path[:0] = [str(CHIP), str(CHIP.parents[1] / "src")]

import bench  # noqa: E402

DATA = HERE / "data"
# the tiny model's own limit, set like a cell's: above the program's
# widest gap over seeds, below the control's
LIMITS = {"max_logit_gap": 0.08}
CONTROL = "fp8"  # the control the cells are held against


def _spec():
    spec = json.loads((CHIP.parents[1] / "BENCHMARK.json").read_text())
    for m in spec["end_to_end"] + spec["per_layer"]:
        m.pop("workloads", None)
    spec["configs"] = [{"name": "tiny",
                        "file": "benchmarks/chip/tests/data/tiny.json"}]
    spec["workloads"] = [{"name": "tiny.sessions", "config": "tiny",
                          "traffic": "tiny-sessions", "chips": 1}]
    return spec


def _run(seed, tamper=None, control=False):
    return bench.run_cell(_spec(), "tiny.sessions", seed, 2.0, False,
                          on_chip=False, limits=LIMITS, control=control,
                          tamper=tamper, traffic_dir=DATA,
                          # a frozen engine never drains: give up sooner
                          drain_limit_s=10.0, log=lambda m: None)


@pytest.fixture(scope="module")
def sound():
    return _run(2 ** 31 + 11, control=True)


def test_sound_run_is_correct_and_control_is_not(sound):
    assert sound["correct"], sound["checks"]
    ctl = sound["_extra"]["control"][CONTROL]
    assert any(ctl[k] > lim for k, lim in LIMITS.items()), ctl


def _state_unchanged(engine):
    import jax
    import jax.numpy as jnp

    ex = engine.executor
    decode = ex.decode

    def frozen(st, n, paged):
        keep = jax.tree.map(jnp.copy, dict(st))  # the call donates st
        toks = decode(st, n, paged)
        st.update(keep)  # the step's new caches and counters are dropped
        return toks

    ex.decode = frozen


def _token_altered(engine):
    sched = engine.sched
    rec = sched.reconcile
    vocab = engine.model.cfg.vocab_size
    state = {"done": 0}

    def altered(block, slots, *a, **k):
        if state["done"] < 3:
            block = block.copy()
            for i in range(block.shape[1]):
                live = (block[:, i] >= 0).nonzero()[0]
                if len(live):
                    j = live[0]
                    block[j, i] = (block[j, i] + vocab // 2) % vocab
                    state["done"] += 1
                    break
        return rec(block, slots, *a, **k)

    sched.reconcile = altered


@pytest.mark.parametrize("fault", [_state_unchanged, _token_altered],
                         ids=["state_unchanged", "token_altered"])
def test_fault_makes_run_incorrect(fault):
    out = _run(2 ** 31 + 11, tamper=fault)
    assert not out["correct"], out["checks"]
