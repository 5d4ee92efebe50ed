"""Training driver: ``python -m repro.launch.train --arch <id> [...]``.

Runs real steps on whatever devices exist (CPU smoke → TPU pod): builds the
mesh, shards state via the production rules, restores the newest checkpoint
if present (elastic — the mesh may differ from the one that wrote it),
installs the preemption handler, and train-loops with periodic atomic
checkpoints and straggler heartbeats.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs
from repro.launch import mesh as pmesh
from repro.launch import sharding as shd
from repro.training import checkpoint, fault_tolerance, optimizer as opt
from repro.training import train_loop
from repro.models import transformer as tf


def synthetic_batch(cfg, B, S, step, seed=0):
    rng = np.random.default_rng(seed + step)
    batch = {"tokens": jnp.asarray(rng.integers(1, cfg.vocab, (B, S)),
                                   jnp.int32),
             "labels": jnp.asarray(rng.integers(1, cfg.vocab, (B, S)),
                                   jnp.int32)}
    if cfg.family == "encdec":
        batch["frames"] = jnp.asarray(
            rng.normal(size=(B, cfg.enc_seq, cfg.d_model)), jnp.bfloat16)
    if cfg.frontend == "vision":
        batch["embeds"] = jnp.asarray(
            rng.normal(size=(B, S, cfg.d_model)), jnp.bfloat16)
        batch.pop("tokens")
    return batch


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--arch", required=True)
    p.add_argument("--steps", type=int, default=100)
    p.add_argument("--batch", type=int, default=8)
    p.add_argument("--seq", type=int, default=128)
    p.add_argument("--reduced", action="store_true",
                   help="shrink the config for CPU runs")
    p.add_argument("--lr", type=float, default=3e-4)
    p.add_argument("--accum", type=int, default=1)
    p.add_argument("--ckpt-dir", default=None)
    p.add_argument("--ckpt-every", type=int, default=50)
    p.add_argument("--mesh", default="auto",
                   help="'auto' (all devices × 1) or 'DxM'")
    p.add_argument("--dtype", default="float32")
    args = p.parse_args()

    cfg = configs.get_config(args.arch)
    if args.reduced:
        cfg = configs.reduced(cfg)
    dtype = dict(float32=jnp.float32, bfloat16=jnp.bfloat16)[args.dtype]

    n_dev = len(jax.devices())
    if args.mesh == "auto":
        mesh = pmesh.make_mesh((n_dev, 1), ("data", "model"))
    else:
        d, m = (int(x) for x in args.mesh.split("x"))
        mesh = pmesh.make_mesh((d, m), ("data", "model"))

    ocfg = opt.AdamWConfig(lr=args.lr, warmup_steps=10,
                           decay_steps=max(args.steps, 100))
    state = train_loop.init_train_state(cfg, jax.random.PRNGKey(0),
                                        dtype=dtype, opt_cfg=ocfg)
    state_sh = shd.params_shardings(state, mesh)
    state = jax.tree.map(jax.device_put, state, state_sh)

    start_step = 0
    run = fault_tolerance.RunState()
    if args.ckpt_dir and checkpoint.latest_step(args.ckpt_dir) is not None:
        state, manifest = checkpoint.restore(args.ckpt_dir, state,
                                             shardings=state_sh)
        run = fault_tolerance.RunState.from_dict(manifest.get("extra", {}))
        start_step = run.step + 1
        print(f"# resumed from step {run.step} "
              f"(data_position {run.data_position})")

    step_fn = jax.jit(
        train_loop.make_train_step(cfg, opt_cfg=ocfg,
                                   accum_steps=args.accum),
        in_shardings=(state_sh, shd.batch_shardings(
            synthetic_batch(cfg, args.batch, args.seq, 0), mesh)),
    )
    handler = fault_tolerance.PreemptionHandler().install()
    monitor = fault_tolerance.StragglerMonitor()

    for step in range(start_step, args.steps):
        t0 = time.time()
        batch = synthetic_batch(cfg, args.batch, args.seq, step)
        state, metrics = step_fn(state, batch)
        loss = float(metrics["loss"])
        dt = time.time() - t0
        monitor.beat(f"host{jax.process_index()}", dt)
        if step % 10 == 0 or step == args.steps - 1:
            tok_s = args.batch * args.seq / dt
            print(f"step {step:5d} loss {loss:.4f} "
                  f"gnorm {float(metrics['grad_norm']):.3f} "
                  f"{dt*1e3:.0f} ms ({tok_s:.0f} tok/s)", flush=True)
        want_ckpt = args.ckpt_dir and (
            step % args.ckpt_every == 0 or handler.preempted()
            or step == args.steps - 1)
        if want_ckpt:
            run = fault_tolerance.RunState(
                step=step, data_position=(step + 1) * args.batch)
            checkpoint.save(args.ckpt_dir, step, state,
                            extra=run.to_dict())
        if handler.preempted():
            print(f"# preempted at step {step}; checkpointed and exiting")
            return
    print("# done")


if __name__ == "__main__":
    main()
