#!/usr/bin/env python3
"""Readings that set a training cell's limits, at the cell's own size.

    python bench/calibrate.py --workload qwen05b.train_resume \
        --seeds 101,102,103 --variants control,half_batch

For each seed it runs the plain reference, then each variant put in the
program's place, and prints the numbers ``correct`` compares, one JSON
line per seed and variant:

  control     the reference with every matrix product's operands in
              float8 (e4m3), the precision below the bfloat16 the
              configuration computes in
  half_batch  the reference on half of each batch, the mean taken over
              the rest

A state left unchanged reads 1 on ``change_gap`` and needs no run.

For a serving cell the control needs the program's served tokens: each
seed runs the cell (set-up and a short window at its own load) and
judges the float8 model's choices at the same places instead of the
served tokens.  The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]
VARIANTS = {"control": {"precision": "fp8"},
            "half_batch": {"half_batch": True}}


def readings(workload: str, seeds, variants, *, config_overrides=None,
             traffic_overrides=None, require_tpu: bool = True,
             seconds: float = 30.0, runs_root=None):
    from bench import compare, harness
    from bench import train_common as C
    import jax
    from repro.launch.compile_cache import use_compile_cache

    if require_tpu and jax.devices()[0].platform != "tpu":
        raise harness.BenchError("JAX found no TPU")
    use_compile_cache()
    w = harness.find_workload(workload)
    traffic = dict(w["traffic_spec"], **(traffic_overrides or {}))
    if harness.load_module("drivers", traffic["driver"]).KIND == "serve":
        return _serve(workload, seeds, seconds, config_overrides,
                      traffic_overrides, require_tpu,
                      runs_root or harness.RUNS)
    steps = (traffic["check_steps"] if "check_steps" in traffic
             else traffic["resume_at"] + 1)
    with_grad = "check_steps" in traffic
    out = []
    for seed in seeds:
        cell = harness.Cell(
            name=workload, workload=w, traffic=traffic,
            config=dict(w["config_spec"], **(config_overrides or {})),
            seed=seed, seconds=0.0, trace=False, run_dir=harness.RUNS)
        ref = C.reference(cell, steps)
        for v in variants:
            t0 = time.perf_counter()
            got = C.reference(cell, steps, **VARIANTS[v])
            checks = compare.train_checks(got, ref, with_grad=with_grad)
            out.append({"seed": seed, "variant": v,
                        "seconds": time.perf_counter() - t0,
                        **{c.name: c.value for c in checks}})
            print(json.dumps(out[-1]), flush=True)
    return out


def _serve(workload, seeds, seconds, config_overrides, traffic_overrides,
           require_tpu, runs_root):
    from bench import harness
    out = []
    for seed in seeds:
        args = argparse.Namespace(workload=workload, seed=seed,
                                  seconds=seconds, trace=0)
        res = harness.execute(
            args, t_start=time.perf_counter(), require_tpu=require_tpu,
            config_overrides=config_overrides,
            traffic_overrides=dict(traffic_overrides or {},
                                   variant="control"),
            runs_root=runs_root)
        out.append({"seed": seed, "variant": "control",
                    **{k: c["value"] for k, c in res["checks"].items()}})
        print(json.dumps(out[-1]), flush=True)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--variants", default="control,half_batch")
    ap.add_argument("--seconds", type=float, default=30.0,
                    help="window of a serving cell's run")
    args = ap.parse_args(argv)
    for p in (ROOT / "src", ROOT):
        sys.path.insert(0, str(p))
    readings(args.workload, [int(s) for s in args.seeds.split(",")],
             args.variants.split(","), seconds=args.seconds)
    return 0


if __name__ == "__main__":
    sys.exit(main())
