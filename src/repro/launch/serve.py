"""Serving launcher: batched greedy decode with serving-state snapshots.

  PYTHONPATH=src python -m repro.launch.serve --arch qwen1.5-0.5b --smoke \
      --batch 4 --prompt-len 16 --tokens 32 [--snapshot-at 16] [--restore]

--snapshot-at N checkpoints the half-finished generation (KV cache +
cursor) after N tokens; --restore resumes it in a fresh process — the
Modal/MemVerge serving cold-start story (paper §6 Real-World Deployments).
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
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--policy", default="baseline")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--tokens", type=int, default=32)
    ap.add_argument("--max-seq", type=int, default=128)
    ap.add_argument("--run-dir", default="runs/serve")
    ap.add_argument("--snapshot-at", type=int, default=None)
    ap.add_argument("--restore", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    from repro.configs import get_config, get_smoke_config
    from repro.data import TokenPipeline
    from repro.launch.compile_cache import use_compile_cache
    from repro.launch.mesh import make_host_mesh
    from repro.models.encdec import build_model
    from repro.runtime.server import DecodeServer
    from repro.sharding import get_policy

    use_compile_cache()
    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    mesh = make_host_mesh(data=len(jax.devices()), model=1)
    policy = get_policy(args.policy)

    srv = DecodeServer(cfg, policy, mesh, args.run_dir,
                       max_seq=args.max_seq,
                       compute_dtype=jnp.float32 if args.smoke
                       else jnp.bfloat16)
    model = build_model(cfg, policy, mesh,
                        compute_dtype=jnp.float32 if args.smoke
                        else jnp.bfloat16, remat=False)
    srv.load(model.init(jax.random.key(args.seed)))

    batch = TokenPipeline(cfg, args.batch, args.prompt_len,
                          seed=args.seed).next()
    srv.start(batch)
    if args.restore:
        pos = srv.restore()
        print(f"[serve] restored mid-generation snapshot at pos {pos}")

    remaining = args.tokens - (srv.pos - args.prompt_len)
    if args.snapshot_at is not None and not args.restore:
        first = min(args.snapshot_at, remaining)
        srv.decode(first)
        path = srv.checkpoint(0)
        print(f"[serve] serving snapshot at pos {srv.pos} -> {path}")
        remaining -= first
    srv.decode(max(remaining, 0))

    out = srv.tokens
    print(json.dumps({
        "arch": cfg.name,
        "generated": int(out.shape[1] - args.prompt_len),
        "tokens_preview": out[0, args.prompt_len:args.prompt_len + 12]
        .tolist(),
        "pos": srv.pos,
    }, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
