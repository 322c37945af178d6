"""Production training driver: mesh discovery, sharded train step, LSM-dedup
data pipeline, fault-tolerant supervised loop, checkpoint/restart.

  PYTHONPATH=src python -m repro.launch.train --arch stablelm-1.6b --smoke \
      --steps 200 --ckpt-dir /tmp/ckpt

On real hardware the same entry point scales: the mesh is built from whatever
devices the runtime exposes (data x model best-fit), and restart under a
different device count is handled by the elastic checkpoint restore.
"""

from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
from jax.sharding import AxisType
import numpy as np

from repro.checkpoint.checkpoint import CheckpointManager
from repro.configs.base import ARCH_IDS, get_config, get_smoke_config
from repro.data.pipeline import PipelineConfig, dedup_batch, make_batch, pipeline_init
from repro.dist import sharding as shd
from repro.dist.fault_tolerance import StragglerMonitor, TrainSupervisor
from repro.models import model_zoo as zoo
from repro.optim.adam import AdamConfig, adam_init
from repro.train.steps import make_train_step


def best_fit_mesh():
    n = len(jax.devices())
    model = 1
    for m in (16, 8, 4, 2, 1):
        if n % m == 0 and m <= n:
            model = m
            break
    return jax.make_mesh(
        (n // model, model), ("data", "model"),
        axis_types=(AxisType.Auto,) * 2,
    )


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, default="stablelm-1.6b")
    ap.add_argument("--smoke", action="store_true", help="reduced config (CPU-friendly)")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--save-every", type=int, default=50)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--no-dedup", action="store_true")
    ap.add_argument("--fail-at", type=int, default=-1,
                    help="inject a worker failure at this step (FT demo)")
    args = ap.parse_args(argv)

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    mesh = best_fit_mesh()
    print(f"[train] arch={cfg.name} mesh={dict(mesh.shape)} devices={len(jax.devices())}")

    ocfg = AdamConfig(lr=args.lr, total_steps=args.steps, warmup_steps=max(10, args.steps // 20))
    key = jax.random.PRNGKey(0)
    params = zoo.init_params(cfg, key)
    opt_state = adam_init(ocfg, params)
    n_params = sum(int(np.prod(l.shape)) for l in jax.tree_util.tree_leaves(params))
    print(f"[train] params: {n_params/1e6:.1f}M")

    params_sh = shd.params_shardings(cfg, params, mesh)
    opt_sh = type(opt_state)(
        m=shd.params_shardings(cfg, opt_state.m, mesh),
        v=shd.params_shardings(cfg, opt_state.v, mesh),
        step=shd.replicated(mesh),
    )
    params = jax.device_put(params, params_sh)
    opt_state = jax.device_put(opt_state, opt_sh)

    step_fn_raw = make_train_step(cfg, ocfg)
    metrics_sh = {k: shd.replicated(mesh) for k in ("loss", "aux_loss", "grad_norm", "lr")}

    pcfg = PipelineConfig(
        vocab_size=cfg.vocab_size, seq_len=args.seq, batch_per_shard=args.batch,
        dedup=not args.no_dedup,
    )
    pipe_state = pipeline_init(pcfg)

    sample = make_batch(pcfg, 0, 0)
    batch_sh = shd.batch_shardings(sample, mesh)
    jitted = jax.jit(
        step_fn_raw,
        in_shardings=(params_sh, opt_sh, batch_sh),
        out_shardings=(params_sh, opt_sh, metrics_sh),
        donate_argnums=(0, 1),
    )

    ckpt = CheckpointManager(args.ckpt_dir, keep=3, async_save=True)
    sup = TrainSupervisor(ckpt, save_every=args.save_every,
                          monitor=StragglerMonitor())

    start_step = 0
    if args.resume and ckpt.latest_step() is not None:
        start_step = ckpt.latest_step()
        spec = {"params": params, "opt": opt_state}
        restored = ckpt.restore(start_step, spec,
                                shardings={"params": params_sh, "opt": opt_sh})
        params, opt_state = restored["params"], restored["opt"]
        print(f"[train] resumed from step {start_step}")

    state = {"params": params, "opt": opt_state, "pipe": pipe_state}
    losses = []
    fail_at = {args.fail_at} if args.fail_at >= 0 else set()
    t_start = time.time()

    def step_fn(state, step):
        if step in fail_at:
            fail_at.clear()
            raise RuntimeError("injected failure (FT demo)")
        batch = make_batch(pcfg, 0, step)
        pipe, batch, n_dup = dedup_batch(pcfg, state["pipe"], batch, 0, step)
        p, o, metrics = jitted(state["params"], state["opt"], batch)
        if step % args.log_every == 0:
            loss = float(metrics["loss"])
            losses.append(loss)
            dt = time.time() - t_start
            tok_s = (step - start_step + 1) * args.batch * args.seq / max(dt, 1e-9)
            print(f"  step {step:5d} loss {loss:.4f} gnorm {float(metrics['grad_norm']):.3f} "
                  f"lr {float(metrics['lr']):.2e} dups {int(n_dup)} tok/s {tok_s:,.0f}",
                  flush=True)
        return {"params": p, "opt": o, "pipe": pipe}

    sup_state, done = sup.run(state, step_fn, num_steps=args.steps, start_step=start_step)
    ckpt.wait()
    if sup.log:
        print("[train] supervisor log:")
        for line in sup.log:
            print("   ", line)
    print(f"[train] finished at step {done}; last losses: "
          f"{[round(l, 3) for l in losses[-5:]]}")
    if len(losses) >= 2 and losses[-1] < losses[0]:
        print("[train] loss decreased ✓")
    return losses


if __name__ == "__main__":
    main()
