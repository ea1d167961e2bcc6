"""Production training launcher.

On real hardware this runs under `jax.distributed.initialize()` with one
process per host; on this container it runs the same code on the local
mesh.  The step function, sharding plan, data pipeline, checkpointing and
straggler monitor are identical to the dry-run's - the dry-run proves this
program lowers for the production meshes.

    PYTHONPATH=src python -m repro.launch.train --arch smollm-360m-smoke --steps 20
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro.configs.registry import get
from repro.data.pipeline import DataConfig, Pipeline
from repro.launch.cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.launch.steps import make_train_step
from repro.parallel import policy
from repro.parallel.sharding import ShardingPlan
from repro.train import optim
from repro.train.loop import StragglerMonitor
from repro.ckpt import checkpoint as ckpt
from repro.arch.model_zoo import build


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-360m-smoke")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--microbatches", type=int, default=2)
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=10)
    args = ap.parse_args()

    enable_compile_cache()
    cfg = get(args.arch)
    mesh = make_host_mesh()
    plan = ShardingPlan(mesh)
    model = build(cfg)

    with mesh, policy.activate(mesh):
        params = model.init(jax.random.PRNGKey(0))
        opt_state = optim.init_state(params)
        pspec = plan.param_spec(jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), params))
        params = jax.device_put(params, plan.named(pspec))
        opt_state = jax.device_put(
            opt_state, plan.named(plan.opt_state_spec(pspec)))

        step_fn = jax.jit(
            make_train_step(cfg, optim.AdamWConfig(
                lr=3e-3, warmup_steps=10, total_steps=args.steps)),
            donate_argnums=(0, 1),
        )
        dcfg = DataConfig(vocab=cfg.vocab, seq_len=args.seq,
                          global_batch=args.batch)
        pipe = Pipeline(dcfg)
        monitor = StragglerMonitor()
        saver = ckpt.AsyncCheckpointer(args.ckpt_dir) if args.ckpt_dir else None
        mb = args.microbatches
        try:
            for step, batch in pipe:
                if step >= args.steps:
                    break
                t0 = time.perf_counter()
                shaped = {
                    k: jnp.asarray(v).reshape((mb, -1) + v.shape[1:])
                    for k, v in batch.items()
                }
                params, opt_state, metrics = step_fn(params, opt_state, shaped)
                dt = time.perf_counter() - t0
                monitor.record(step, dt)
                if step % 5 == 0:
                    print(f"step {step} loss {float(metrics['loss']):.4f} "
                          f"{dt*1e3:.0f}ms")
                if saver and (step + 1) % args.ckpt_every == 0:
                    saver.save_async(step + 1,
                                     {"params": params, "opt": opt_state},
                                     extra={"next_step": step + 1})
        finally:
            pipe.close()
            if saver:
                saver.wait()
        print(f"done; stragglers: {len(monitor.flagged)}")


if __name__ == "__main__":
    main()
