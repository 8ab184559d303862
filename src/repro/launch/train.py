"""End-to-end training driver with fault tolerance.

Runs on whatever devices exist: the host mesh (CPU dev loop / smoke) or the
production pod mesh on TPU.  Features:

  * auto-resume: restores the latest atomic checkpoint if one exists —
    restart-after-failure IS the fault-tolerance path (kill the process at
    any step; relaunching continues from the last checkpoint);
  * elastic re-shard: checkpoints are device-count-agnostic (host-flat
    npz); restore re-places leaves onto the CURRENT mesh, so a job saved
    on N chips restores onto M;
  * async checkpointing off the critical path (``--ckpt-blocking`` to
    force synchronous writes);
  * deterministic data: batch t is a pure function of (seed, t), so a
    resumed run consumes exactly the tokens a never-failed run would.

Examples:
  PYTHONPATH=src python -m repro.launch.train --arch smollm-135m --reduced \
      --steps 50 --batch 8 --seq 128 --ckpt-dir /tmp/ckpt
  PYTHONPATH=src python -m repro.launch.train --arch smollm-135m \
      --steps 200 --batch 32 --seq 1024   # full config, real mesh
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.configs import get_config, reduced as reduce_cfg
from repro.distribution import sharding as shd
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.models import bundle
from repro.training import data as data_mod
from repro.training import optimizer as opt
from repro.training.checkpoint import CheckpointManager
from repro.training.train_loop import TrainConfig, make_train_step


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-135m")
    ap.add_argument("--reduced", action="store_true", help="tiny config (CPU)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--microbatch", type=int, default=0)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=25)
    ap.add_argument("--ckpt-blocking", action="store_true")
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--no-fsdp", action="store_true")
    args = ap.parse_args()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduce_cfg(cfg, capacity_factor=8.0)
    mb = bundle(cfg)
    mesh = make_host_mesh()
    fsdp = not args.no_fsdp
    print(f"arch={cfg.name} params={mb.param_count():,} mesh={dict(mesh.shape)}")

    ocfg = opt.AdamWConfig(lr=args.lr)
    tcfg = TrainConfig(microbatch=args.microbatch, remat=True)
    step_fn = make_train_step(mb, ocfg, tcfg)
    dcfg = data_mod.DataConfig(
        vocab_size=cfg.vocab_size, seq_len=args.seq, global_batch=args.batch,
        seed=args.seed, frontend=cfg.frontend or ("audio" if cfg.enc_dec else None),
        frontend_len=cfg.frontend_len, frontend_dim=cfg.frontend_dim,
        dtype=cfg.dtype,
    )

    with shd.use_mesh(mesh, fsdp=fsdp):
        params = mb.init(jax.random.key(args.seed))
        opt_state = opt.init(params, ocfg)
        pnamed = shd.named(shd.param_specs(params, mesh, fsdp), mesh)
        onamed = shd.named(shd.opt_state_specs(params, opt_state, mesh, fsdp), mesh)
        params = jax.tree.map(jax.device_put, params, pnamed)
        opt_state = jax.tree.map(jax.device_put, opt_state, onamed)

        start = 0
        ckpt = CheckpointManager(args.ckpt_dir) if args.ckpt_dir else None
        if ckpt is not None:
            latest = ckpt.latest_step()
            if latest is not None:
                params, opt_state = ckpt.restore(
                    latest, params, opt_state, shardings=(pnamed, onamed)
                )
                start = latest + 1
                print(f"resumed from step {latest}")

        jitted = jax.jit(
            step_fn,
            in_shardings=(pnamed, onamed, None),
            out_shardings=(pnamed, onamed, None),
            donate_argnums=(0, 1),
        )

        losses = []
        t0 = time.time()
        for step in range(start, args.steps):
            batch = data_mod.shard_batch(data_mod.get_batch(dcfg, step), mesh)
            params, opt_state, metrics = jitted(params, opt_state, batch)
            loss = float(metrics["loss"])
            losses.append(loss)
            if np.isnan(loss):
                raise FloatingPointError(f"NaN loss at step {step}")
            if step % args.log_every == 0 or step == args.steps - 1:
                dt = time.time() - t0
                tput = args.batch * args.seq * args.log_every / max(dt, 1e-9)
                print(f"step {step:5d} loss {loss:8.4f} ({dt:5.1f}s, {tput:,.0f} tok/s)")
                t0 = time.time()
            if ckpt is not None and (step + 1) % args.ckpt_every == 0:
                ckpt.save(step, params, opt_state, blocking=args.ckpt_blocking)
        if ckpt is not None:
            ckpt.save(args.steps - 1, params, opt_state, blocking=True)
            ckpt.wait()
        first = np.mean(losses[: max(1, len(losses) // 10)])
        last = np.mean(losses[-max(1, len(losses) // 10):])
        print(f"loss {first:.4f} -> {last:.4f} "
              f"({'improved' if last < first else 'NOT improved'})")
        return 0 if last < first else 1


if __name__ == "__main__":
    enable_compile_cache()
    raise SystemExit(main())
