"""Training driver: step factory + fault-tolerant CLI loop.

`make_train_step` is shared by the dry-run (lower/compile only) and the real
CPU-scale training example: one jit'd SPMD program computing
loss -> grad -> clip -> optimizer update, params/opt-state donated, sharded
per the Cluster Builder plan.  Gradient cross-pod reduction is implicit in
SPMD data parallelism; the GMI/compressed variants are exercised separately
(core/gmi.py, optim/compression.py) and compared in §Perf.

The CLI loop adds the production substrate: deterministic data pipeline,
async checkpointing, failure injection + recovery, straggler monitoring.

Usage (CPU example, reduced config):
  PYTHONPATH=src python -m repro.launch.train --arch smollm-135m --reduced \
      --steps 60 --batch 8 --seq 64
"""
from __future__ import annotations

import argparse
import functools
import logging
import time
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import CheckpointManager
from repro.configs import get_config
from repro.core.cluster_builder import build_plan
from repro.data.pipeline import TokenPipeline, shard_batch
from repro.launch.compile_cache import enable_compile_cache
from repro.models.transformer import Model, init_params, make_model
from repro.optim.optimizer import (
    clip_by_global_norm, cosine_schedule, make_optimizer,
)
from repro.runtime import FailureInjector, StragglerMonitor, run_with_recovery

log = logging.getLogger("repro.train")


def pick_optimizer(cfg) -> str:
    """adamw8 (int8 moments) for models whose f32 Adam state would not fit
    v5e HBM under full FSDP (DESIGN.md §2); f32 AdamW otherwise."""
    return "adamw8" if cfg.param_count() > 50e9 else "adamw"


def make_train_step(model: Model, opt_update, max_grad_norm: float = 1.0,
                    n_micro: int = 1, grad_shardings: Any = None):
    """One jit'd SPMD step; n_micro > 1 scans over gradient-accumulation
    microbatches so per-device live activations stay within HBM (the
    production memory lever for the 33B/400B train cells — DESIGN.md §3).

    grad_shardings (optional pytree of NamedSharding mirroring params) pins
    the f32 accumulator to the parameter sharding — without it XLA is free
    to replicate the accumulator (observed: 64GB/device expert-grad buffers
    on the 400B MoE)."""

    def grad_fn(params, batch):
        return jax.value_and_grad(model.loss)(params, batch)

    def pin(tree):
        if grad_shardings is None:
            return tree
        return jax.tree.map(jax.lax.with_sharding_constraint, tree,
                            grad_shardings)

    def train_step(params, opt_state, batch):
        if n_micro == 1:
            loss, grads = grad_fn(params, batch)
        else:
            micro = jax.tree.map(
                lambda x: x.reshape((n_micro, x.shape[0] // n_micro)
                                    + x.shape[1:]), batch)

            def acc_body(carry, mb):
                loss_acc, g_acc = carry
                loss, g = grad_fn(params, mb)
                g_acc = pin(jax.tree.map(
                    lambda a, b: a + b.astype(jnp.float32), g_acc, g))
                return (loss_acc + loss, g_acc), None

            g0 = pin(jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params))
            (loss, grads), _ = jax.lax.scan(
                acc_body, (jnp.float32(0.0), g0), micro)
            loss = loss / n_micro
            grads = jax.tree.map(lambda g: g / n_micro, grads)
        grads, gnorm = clip_by_global_norm(grads, max_grad_norm)
        params, opt_state = opt_update(grads, opt_state, params)
        metrics = {"loss": loss.astype(jnp.float32),
                   "grad_norm": gnorm.astype(jnp.float32)}
        return params, opt_state, metrics

    return train_step


def default_micro_batches(cfg, global_batch: int, seq_len: int, dp: int,
                          act_budget_bytes: float = 0) -> int:
    """Smallest microbatch count (dividing the per-replica batch) that keeps
    remat-saved per-layer activations under the budget.

    MoE archs get a larger activation budget: every microbatch re-gathers
    the FSDP'd expert weights, so fewer/larger microbatches trade HBM for
    collective bytes (§Perf B1: 4x fewer expert gathers on the 400B)."""
    if not act_budget_bytes:
        act_budget_bytes = 12e9 if cfg.n_experts else 4e9
    b_loc = max(global_batch // dp, 1)
    per_row = seq_len * cfg.d_model * 2 * max(cfg.n_layers, 1)
    mb_loc = max(1, int(act_budget_bytes // max(per_row, 1)))
    n = max(1, -(-b_loc // mb_loc))
    while b_loc % n:
        n += 1
    return min(n, b_loc)


def jit_train_step(model: Model, opt_update, plan, opt_specs) -> Any:
    """jit with Cluster-Builder shardings; donates params+opt state."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    def ns(spec):
        return NamedSharding(plan.mesh, spec)

    param_sh = jax.tree.map(ns, plan.param_specs)
    opt_sh = jax.tree.map(ns, opt_specs)
    repl = NamedSharding(plan.mesh, P())
    step = make_train_step(model, opt_update)
    return jax.jit(
        step,
        in_shardings=(param_sh, opt_sh, None),
        out_shardings=(param_sh, opt_sh, {"loss": repl, "grad_norm": repl}),
        donate_argnums=(0, 1),
    )


def opt_state_specs(opt_state_shape, param_specs, mesh=None) -> Any:
    """Optimizer-state PartitionSpecs.

    f32 moments mirror their parameter (ZeRO: state lives with the param
    shard).  Block-quantized int8 moments are flat (nblk, BLOCK) arrays:
    their block dim is sharded across the WHOLE mesh (every chip owns a
    contiguous stripe — fully sharded optimizer state, the point of
    adamw8); scalars replicate."""
    from jax.sharding import PartitionSpec as P

    def blk_spec(sub, spec_sub):
        # param-shaped int8 moment: q shards exactly like the param; the
        # per-block scale drops the last (blocked) axis assignment
        if not hasattr(spec_sub, "__len__") or len(spec_sub) != sub["q"].ndim:
            return {"q": P(), "s": P()}
        qspec = spec_sub
        sspec = P(*(tuple(spec_sub[:-1]) + (None,)))
        return {"q": qspec, "s": sspec}

    def go(sub, spec_sub):
        if isinstance(sub, dict) and "q" in sub and "s" in sub:
            return blk_spec(sub, spec_sub)
        if isinstance(sub, dict):
            return {k: go(v, spec_sub.get(k) if isinstance(spec_sub, dict)
                          else spec_sub) for k, v in sub.items()}
        if spec_sub is None or not hasattr(sub, "shape") or sub.ndim == 0:
            return P()
        return spec_sub if sub.ndim == len(spec_sub) else P()

    out = {}
    for key, sub in opt_state_shape.items():
        if key in ("m", "v"):
            out[key] = go(sub, param_specs)
        else:
            out[key] = jax.tree.map(lambda _: P(), sub)
    return out


# ---------------------------------------------------------------------------
# CLI loop
# ---------------------------------------------------------------------------


def main(argv=None) -> Dict[str, Any]:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-135m")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=60)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--optimizer", default="")
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=20)
    ap.add_argument("--pack", action="store_true",
                    help="no-padding packed sequences (paper §7.1)")
    ap.add_argument("--inject-failure-at", type=int, default=-1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    model = make_model(cfg)
    opt_name = args.optimizer or pick_optimizer(cfg)
    lr_fn = cosine_schedule(args.lr, warmup=max(args.steps // 20, 5),
                            total=args.steps)
    opt_init, opt_update = make_optimizer(opt_name, lr_fn)
    step_fn = jax.jit(make_train_step(model, opt_update),
                      donate_argnums=(0, 1))

    pipe = TokenPipeline(cfg.vocab_size, args.batch, args.seq,
                         seed=args.seed, pack=args.pack)
    ckpt = CheckpointManager(args.ckpt_dir, keep=2)
    injector = FailureInjector(
        {args.inject_failure_at: "node_loss"}
        if args.inject_failure_at >= 0 else {})
    monitor = StragglerMonitor()
    losses: list = []

    def make_state():
        params = init_params(cfg, jax.random.PRNGKey(args.seed))
        return {"params": params, "opt": opt_init(params)}

    def train_steps(state, start, stop):
        params, opt = state["params"], state["opt"]
        for step in range(start, stop):
            injector.check(step)
            batch = {k: jnp.asarray(v)
                     for k, v in pipe.next_batch().items()}
            t0 = time.perf_counter()
            params, opt, metrics = step_fn(params, opt, batch)
            monitor.observe(step, time.perf_counter() - t0)
            loss = float(metrics["loss"])
            losses.append(loss)
            if step % 10 == 0:
                log.info("step %d loss %.4f", step, loss)
        return {"params": params, "opt": opt}

    def save(step, state):
        ckpt.save(step, {"params": state["params"]})

    def restore():
        ckpt.wait()  # an in-flight async save must land before we pick
        latest = ckpt.latest_step()
        if latest is None:
            return None
        state = make_state()
        step, tree = ckpt.restore(latest,
                                  template={"params": state["params"]})
        return step, {"params": tree["params"], "opt": state["opt"]}

    state, report = run_with_recovery(
        make_state, train_steps, save, restore,
        total_steps=args.steps, checkpoint_every=args.ckpt_every)
    ckpt.wait()
    first = np.mean(losses[:10]) if len(losses) >= 10 else losses[0]
    last = np.mean(losses[-10:])
    log.info("loss %.4f -> %.4f, restarts=%d", first, last, report.restarts)
    print(f"train: arch={cfg.name} opt={opt_name} steps={args.steps} "
          f"loss {first:.4f} -> {last:.4f} restarts={report.restarts} "
          f"stragglers={len(monitor.events)}")
    return {"losses": losses, "report": report, "state": state}


if __name__ == "__main__":
    logging.basicConfig(level=logging.INFO)
    main()
