"""Training driver: sharded train_step builder + a runnable host-scale loop.

``make_train_step``/``shard_train_step`` are the production path: the same
code lowers on the (16,16)/(2,16,16) meshes in the dry-run and runs on a
host mesh in tests/examples.  GSPMD inserts the DP gradient all-reduce (the
parameters are replicated over pod/data and the batch is sharded, so the
backward pass psums automatically); TP/EP collectives come from the
parameter PartitionSpecs in distributed/sharding.py.
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, get_reduced
from repro.distributed.fault_tolerance import CheckpointManager, StepWatchdog
from repro.distributed.sharding import (
    batch_shardings,
    param_shardings,
)
from repro.launch.mesh import make_mesh
from repro.models.model import Model, build
from repro.optim import adamw


def make_train_step(model: Model, opt_cfg: adamw.AdamWConfig):
    def train_step(params, opt_state, batch):
        (loss, metrics), grads = jax.value_and_grad(model.loss, has_aux=True)(
            params, batch
        )
        params, opt_state, opt_metrics = adamw.update(opt_cfg, params, grads, opt_state)
        return params, opt_state, {"loss": loss, **metrics, **opt_metrics}

    return train_step


def shard_train_step(model: Model, mesh, opt_cfg: adamw.AdamWConfig, batch_example):
    """Returns (jitted step, params_sharding, opt_sharding, batch_sharding)."""
    params_shape = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    p_shard = param_shardings(mesh, params_shape)
    opt_shape = jax.eval_shape(adamw.init, params_shape)
    o_shard = {
        "mu": p_shard,
        "nu": p_shard,
        "step": jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec()),
    }
    b_shard = batch_shardings(mesh, jax.eval_shape(lambda: batch_example))
    step = jax.jit(
        make_train_step(model, opt_cfg),
        in_shardings=(p_shard, o_shard, b_shard),
        out_shardings=(p_shard, o_shard, None),
        donate_argnums=(0, 1),
    )
    return step, p_shard, o_shard, b_shard


def init_sharded(model: Model, mesh, seed: int = 0):
    """Initialize params/opt state directly into their shardings."""
    params_shape = jax.eval_shape(model.init, jax.random.PRNGKey(seed))
    p_shard = param_shardings(mesh, params_shape)
    params = jax.jit(model.init, out_shardings=p_shard)(jax.random.PRNGKey(seed))
    o_shard = {
        "mu": p_shard,
        "nu": p_shard,
        "step": jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec()),
    }
    opt_state = jax.jit(adamw.init, out_shardings=o_shard)(params)
    return params, opt_state, p_shard, o_shard


def train_loop(
    model: Model,
    mesh,
    *,
    steps: int = 100,
    batch_iter=None,
    opt_cfg: adamw.AdamWConfig | None = None,
    ckpt_dir: str | None = None,
    ckpt_every: int = 50,
    log_every: int = 10,
    resume: bool = True,
):
    opt_cfg = opt_cfg or adamw.AdamWConfig(total_steps=steps)
    example = next(batch_iter)
    example = jax.tree.map(jnp.asarray, example)
    step_fn, p_shard, o_shard, b_shard = shard_train_step(model, mesh, opt_cfg, example)
    params, opt_state, _, _ = init_sharded(model, mesh)

    start = 0
    mgr = None
    if ckpt_dir:
        mgr = CheckpointManager(ckpt_dir, every=ckpt_every)
        if resume:
            like = jax.eval_shape(lambda: {"params": params, "opt": opt_state})
            start, restored = mgr.resume_latest(
                like, {"params": p_shard, "opt": o_shard}
            )
            if restored is not None:
                params, opt_state = restored["params"], restored["opt"]
                print(f"[train] resumed from step {start}")

    watchdog = StepWatchdog()
    history = []
    batch = example
    for step in range(start + 1, steps + 1):
        with watchdog:
            batch_dev = jax.device_put(batch, b_shard)
            params, opt_state, metrics = step_fn(params, opt_state, batch_dev)
            batch = jax.tree.map(jnp.asarray, next(batch_iter))  # overlap host fetch
            loss = float(metrics["loss"])
        history.append(loss)
        if step % log_every == 0 or step == steps:
            print(
                f"[train] step {step:5d} loss {loss:.4f} "
                f"gnorm {float(metrics['grad_norm']):.3f} "
                f"lr {float(metrics['lr']):.2e} "
                f"med_step {watchdog.median*1e3:.0f}ms stragglers {watchdog.stragglers}"
            )
        if mgr:
            mgr.maybe_save(step, {"params": params, "opt": opt_state})
    if mgr:
        mgr.wait()
    return params, opt_state, history


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="yi-9b")
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--mesh", default="2x2")
    ap.add_argument("--ckpt-dir", default=None)
    args = ap.parse_args()

    from repro.data.pipeline import SyntheticLM

    cfg = get_reduced(args.arch) if args.reduced else get_config(args.arch)
    model = build(cfg)
    d0, d1 = (int(x) for x in args.mesh.split("x"))
    mesh = make_mesh((d0, d1))
    data = SyntheticLM(cfg.vocab_size, args.seq, args.batch)
    t0 = time.time()
    _, _, hist = train_loop(
        model, mesh, steps=args.steps, batch_iter=iter(data), ckpt_dir=args.ckpt_dir
    )
    data.close()
    print(f"[train] {args.steps} steps in {time.time()-t0:.1f}s; "
          f"loss {hist[0]:.3f} -> {hist[-1]:.3f}")


if __name__ == "__main__":
    main()
