"""Training launcher.

  PYTHONPATH=src python -m repro.launch.train --arch qwen1.5-0.5b --smoke \
      --steps 200 --ckpt-every 25 --ckpt-mode async [--restore] \
      [--policy baseline] [--fail-at 120]

Without --smoke the full config runs in bf16 on a data-parallel mesh
over every local device; --smoke selects the reduced same-family config
in f32, sized for a CPU.  --restore resumes from the newest valid
unified snapshot in --run-dir (the CRIUgpu restart path).  A crash
drains the in-flight async save before exiting, so the image that
--restore will pick is on disk.
"""
from __future__ import annotations

import argparse
import json
import sys

import jax
import jax.numpy as jnp


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen1.5-0.5b")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced same-family config (CPU-sized)")
    ap.add_argument("--policy", default="baseline")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch-size", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=64)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-every", type=int, default=25)
    ap.add_argument("--ckpt-mode", default="async",
                    choices=["sync", "async"])
    ap.add_argument("--incremental", action="store_true")
    ap.add_argument("--keep", type=int, default=3)
    ap.add_argument("--run-dir", default="runs/train")
    ap.add_argument("--restore", action="store_true",
                    help="resume from the newest valid snapshot")
    ap.add_argument("--fail-at", type=int, default=None,
                    help="inject a crash at this step (fault-tolerance demo)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    from repro.api import CheckpointOptions
    from repro.configs import get_config, get_smoke_config
    from repro.launch.compile_cache import use_compile_cache
    from repro.launch.mesh import make_host_mesh
    from repro.runtime.trainer import TrainConfig, Trainer
    from repro.sharding import get_policy

    use_compile_cache()
    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    n_dev = len(jax.devices())
    mesh = make_host_mesh(data=n_dev, model=1)
    policy = get_policy(args.policy)
    tcfg = TrainConfig(
        batch_size=args.batch_size, seq_len=args.seq_len, lr=args.lr,
        total_steps=args.steps, ckpt_every=args.ckpt_every,
        ckpt=CheckpointOptions(mode=args.ckpt_mode,
                               incremental=args.incremental,
                               keep=args.keep),
        seed=args.seed,
        compute_dtype=jnp.float32 if args.smoke else jnp.bfloat16)

    trainer = Trainer(cfg, tcfg, mesh, policy, args.run_dir)
    if args.restore:
        step = trainer.restore()
        print(f"[train] restored unified snapshot at step {step}")
    else:
        trainer.initialize()

    try:
        out = trainer.run(args.steps - trainer.step, fail_at=args.fail_at)
    except Exception as e:
        print(f"[train] crashed: {e} — restart with --restore", file=sys.stderr)
        # the writer thread is a daemon: exiting now would drop the async
        # save still in flight, and with it the image --restore needs
        trainer.session.wait_pending()
        return 1
    print(json.dumps({
        "arch": cfg.name, "steps": out["steps"], "final_loss": out["loss"],
        "wall_s": round(out["wall_s"], 2),
        "snapshots": trainer.session.store.list_steps(),
    }, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
