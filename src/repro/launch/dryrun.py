import os
os.environ["XLA_FLAGS"] = (os.environ.get("_REPRO_EXTRA_XLA", "") +
                           " --xla_force_host_platform_device_count=512")
# a CPU-compiler estimate: it must never take the accelerator, which
# belongs to one process at a time (and --all starts one child per cell)
os.environ["JAX_PLATFORMS"] = "cpu"
# ^ both MUST run before any jax import: jax locks the platform and the
# device count on first init.

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

For each cell the FULL published config is lowered with abstract inputs
(ShapeDtypeStruct — no allocation) onto the production mesh, compiled, and
the artifacts recorded for EXPERIMENTS.md:

  * ``compiled.memory_analysis()``  — proves the layout fits HBM
  * ``compiled.cost_analysis()``    — HLO FLOPs / bytes for §Roofline
  * collective-op byte census parsed from the partitioned HLO
    (all-gather / all-reduce / reduce-scatter / all-to-all /
    collective-permute) — the §Roofline collective term.

Usage:
  python -m repro.launch.dryrun --arch qwen1.5-0.5b --shape train_4k \
      --mesh pod|multipod [--policy baseline] [--out artifacts/dryrun]
  python -m repro.launch.dryrun --all [--mesh both]   # subprocess per cell
"""
import argparse
import json
import re
import subprocess
import sys
import time
from typing import Any, Dict

# TPU v5e-class hardware constants (roofline targets; CPU is the host here)
PEAK_FLOPS = 197e12          # bf16 / chip
HBM_BW = 819e9               # bytes/s / chip
LINK_BW = 50e9               # bytes/s / ICI link

DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16,
}

_COLL = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
         "collective-permute")
_COLL_RE = re.compile(
    r"=\s*(?P<restype>.+?)\s+"
    r"(?P<op>all-gather|all-reduce|reduce-scatter|all-to-all|"
    r"collective-permute)(?P<variant>-start)?\(",
)
_SHAPE_RE = re.compile(r"([a-z][a-z0-9]*)\[([0-9,]*)\]")
_GROUPS_RE = re.compile(r"replica_groups=\{\{([0-9, ]+)\}")
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")


def _bytes_of(restype: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(restype):
        if dt not in DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * DTYPE_BYTES[dt]
    return total


def parse_collectives(hlo_text: str, n_devices: int) -> Dict[str, Any]:
    """Per-device collective byte census from partitioned HLO."""
    out = {op: {"count": 0, "bytes": 0.0, "wire_bytes": 0.0}
           for op in _COLL}
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if not m:
            continue
        op = m.group("op")
        nbytes = _bytes_of(m.group("restype"))
        g = n_devices
        mg = _GROUPS_RE.search(line)
        if mg:
            g = len(mg.group(1).split(","))
        else:
            mi = _GROUPS_IOTA_RE.search(line)
            if mi:
                g = int(mi.group(2))
        g = max(g, 1)
        # ring-algorithm wire bytes per device
        if op == "all-gather":
            wire = nbytes * (g - 1) / g
        elif op == "all-reduce":
            wire = 2.0 * nbytes * (g - 1) / g
        elif op == "reduce-scatter":
            wire = nbytes * (g - 1)          # result is the scattered shard
        elif op == "all-to-all":
            wire = nbytes * (g - 1) / g
        else:                                 # collective-permute
            wire = float(nbytes)
        out[op]["count"] += 1
        out[op]["bytes"] += float(nbytes)
        out[op]["wire_bytes"] += wire
    out["total_wire_bytes"] = sum(v["wire_bytes"] for k, v in out.items()
                                  if isinstance(v, dict))
    out["total_count"] = sum(v["count"] for k, v in out.items()
                             if isinstance(v, dict))
    return out


# ----------------------------------------------------------------------
VARIANTS = ("base", "bf16score", "xentchunk", "noremat", "gqaexpand",
            "bf16cast", "gradbf16", "gqaexpand_bf16cast",
            "gqaexpand_bf16cast_gradbf16", "opt")


_KNOBS = {"base", "bf16score", "xentchunk", "noremat", "gqaexpand",
          "bf16cast", "gradbf16"}


def variant_parts(variant: str) -> set:
    if variant == "opt":        # every winning knob (see EXPERIMENTS.md)
        return {"gqaexpand", "bf16cast", "gradbf16", "xentchunk"}
    parts = set(variant.split("_"))
    unknown = parts - _KNOBS
    if unknown:
        raise ValueError(f"unknown variant knob(s) {sorted(unknown)}; "
                         f"known: {sorted(_KNOBS)}")
    return parts


def apply_variant(variant: str) -> bool:
    """§Perf hillclimb knobs (module-level, applied before tracing).
    Variants compose with '_'; 'opt' = every winning knob.  Returns the
    remat setting the variant implies."""
    import jax.numpy as jnp
    from repro.models import layers as L
    parts = variant_parts(variant)
    L.SCORE_DTYPE = jnp.bfloat16 if "bf16score" in parts else jnp.float32
    L.XENT_SEQ_CHUNK = 512 if "xentchunk" in parts else 0
    L.GQA_EXPAND = "gqaexpand" in parts
    L.CAST_PARAMS_ONCE = "bf16cast" in parts
    return "noremat" not in parts


def build_lowered(arch: str, shape: str, mesh, policy_name: str,
                  remat: bool = True, variant: str = "base"):
    """Construct and lower the jitted target for one cell."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    if variant != "base":
        remat = apply_variant(variant) and remat

    from repro.configs import get_config
    from repro.launch.shapes import SHAPES, batch_specs, batch_shardings
    from repro.models.encdec import build_model
    from repro.optim import AdamW
    from repro.optim.adamw import OptState
    from repro.optim.schedule import warmup_cosine
    from repro.sharding import get_policy

    from repro.sharding.policy import fit_shardings_tree

    cfg = get_config(arch)
    cell = SHAPES[shape]
    policy = get_policy(policy_name).for_mesh(mesh)
    model = build_model(cfg, policy, mesh, compute_dtype=jnp.bfloat16,
                        remat=remat)
    params_abs = model.init_abstract()
    # divisibility-fit every in_sharding (e.g. whisper d_model=384 cannot
    # shard 256 ways under fsdp_all; prefill batch 32 cannot DP-shard 256
    # ways — the fit degrades to the largest dividing prefix)
    param_sh = fit_shardings_tree(model.param_shardings(), params_abs, mesh)
    scalar_sh = NamedSharding(mesh, P())

    if cell.kind == "train":
        opt = AdamW(lr=warmup_cosine(3e-4, 2000, 100000))
        opt_abs = opt.init_abstract(params_abs)
        opt_sh = OptState(step=scalar_sh, m=param_sh, v=param_sh)
        batch_abs = batch_specs(cfg, cell.global_batch, cell.seq_len)
        batch_sh = fit_shardings_tree(
            batch_shardings(cfg, policy, mesh), batch_abs, mesh)
        grad_bf16 = "gradbf16" in variant_parts(variant)

        def train_step(params, opt_state, batch):
            (loss, metrics), grads = jax.value_and_grad(
                model.loss, has_aux=True)(params, batch)
            if grad_bf16:
                # gradient compression: the cross-replica reduction moves
                # bf16 (half the wire); the optimizer re-upcasts to f32
                grads = jax.tree.map(
                    lambda g: g.astype(jnp.bfloat16).astype(g.dtype), grads)
            params, opt_state, om = opt.update(grads, opt_state, params)
            return params, opt_state, loss, metrics["loss"]

        jitted = jax.jit(train_step,
                         in_shardings=(param_sh, opt_sh, batch_sh),
                         donate_argnums=(0, 1))
        with jax.sharding.set_mesh(mesh):
            lowered = jitted.lower(params_abs, opt_abs, batch_abs)
    elif cell.kind == "prefill":
        batch_abs = batch_specs(cfg, cell.global_batch, cell.seq_len)
        batch_sh = fit_shardings_tree(
            batch_shardings(cfg, policy, mesh), batch_abs, mesh)
        jitted = jax.jit(model.prefill, in_shardings=(param_sh, batch_sh))
        with jax.sharding.set_mesh(mesh):
            lowered = jitted.lower(params_abs, batch_abs)
    else:                                     # decode / serve_step
        B, S = cell.global_batch, cell.seq_len
        cache_abs = model.cache_abstract(B, S)
        cache_sh = model.cache_shardings(batch=B, max_seq=S)
        tok_sh = (policy.sharding(mesh, "batch")
                  if B % _dp_size(policy, mesh) == 0 and
                  _dp_size(policy, mesh) > 1 else scalar_sh)

        def serve_step(params, cache, tokens, pos):
            return model.decode_step(params, cache, tokens, pos)

        jitted = jax.jit(serve_step,
                         in_shardings=(param_sh, cache_sh, tok_sh,
                                       scalar_sh),
                         donate_argnums=(1,))
        with jax.sharding.set_mesh(mesh):
            lowered = jitted.lower(params_abs, cache_abs,
                                   jax.ShapeDtypeStruct((B,), jnp.int32),
                                   jax.ShapeDtypeStruct((), jnp.int32))
    return lowered, cfg, cell


def _dp_size(policy, mesh):
    import numpy as np
    dp = tuple(a for a in policy.dp if a in mesh.axis_names)
    return int(np.prod([mesh.shape[a] for a in dp])) if dp else 1


def analyse(lowered, compiled, cfg, cell, n_devices: int) -> Dict[str, Any]:
    """Three-term roofline from the compiled artifact.

    Primary source: the trip-count-aware HLO analyzer
    (repro.launch.hlo_analysis) — XLA's cost_analysis counts while bodies
    ONCE, undercounting layer-scanned models by ~n_layers; both are
    recorded, the analyzer drives the terms."""
    from repro.launch.hlo_analysis import analyze_hlo, top_buffers

    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0]
    xla_flops = float(cost.get("flops", 0.0))
    xla_bytes = float(cost.get("bytes accessed", 0.0))

    mem: Dict[str, float] = {}
    try:
        ma = compiled.memory_analysis()
        for attr in ("argument_size_in_bytes", "output_size_in_bytes",
                     "temp_size_in_bytes", "alias_size_in_bytes",
                     "generated_code_size_in_bytes"):
            v = getattr(ma, attr, None)
            if v is not None:
                mem[attr] = float(v)
    except Exception as e:                    # pragma: no cover
        mem["error"] = str(e)

    hlo_text = compiled.as_text()
    rec_hlo = analyze_hlo(hlo_text, n_devices,
                          seq_len=cell.seq_len
                          if cell.kind in ("train", "prefill") else None)
    flops_dev = rec_hlo["flops"]
    bytes_dev = rec_hlo["bytes"]
    score_bytes = rec_hlo["score_bytes"]
    coll = {k: v for k, v in rec_hlo["collectives"].items()}
    coll["total_wire_bytes"] = rec_hlo["collective_wire_bytes"]
    coll["total_count"] = rec_hlo["collective_count"]

    # roofline terms (per chip)
    t_compute = flops_dev / PEAK_FLOPS
    t_memory = bytes_dev / HBM_BW
    # kernel-substituted memory term: the validated Pallas flash-attention
    # kernel keeps the (S×S) score/prob matrices in VMEM, so their HBM
    # traffic vanishes (q/k/v/o streaming is already counted by the
    # adjacent projection ops).  This is a MODELLED term — Mosaic cannot
    # lower on the CPU container — and is reported alongside the
    # as-compiled term, never silently substituted.
    t_memory_flash = max(bytes_dev - score_bytes, 0.0) / HBM_BW
    t_coll = coll["total_wire_bytes"] / LINK_BW
    dominant = max(("compute", t_compute), ("memory", t_memory),
                   ("collective", t_coll), key=lambda kv: kv[1])[0]

    # MODEL_FLOPS: 6·N·D train, 2·N_active·D inference
    tokens = (cell.global_batch * cell.seq_len
              if cell.kind in ("train", "prefill") else cell.global_batch)
    n_active = cfg.param_count(active_only=True)
    mf = (6.0 if cell.kind == "train" else 2.0) * n_active * tokens
    hlo_global = flops_dev * n_devices
    ideal_s = mf / n_devices / PEAK_FLOPS
    bound = max(t_compute, t_memory, t_coll)
    bound_flash = max(t_compute, t_memory_flash, t_coll)
    return {
        "flops_per_device": flops_dev,
        "bytes_per_device": bytes_dev,
        "score_bytes_per_device": score_bytes,
        "flops_by_kind": rec_hlo["flops_by_kind"],
        "bytes_by_kind": rec_hlo["bytes_by_kind"],
        "top_traffic": rec_hlo["top_traffic"],
        "top_collectives": rec_hlo["top_collectives"],
        "xla_cost_flops": xla_flops,          # while-body-once (reference)
        "xla_cost_bytes": xla_bytes,
        "collectives": coll,
        "memory": mem,
        "top_buffers": top_buffers(hlo_text, 8),
        "t_compute_s": t_compute,
        "t_memory_s": t_memory,
        "t_memory_flash_s": t_memory_flash,   # modelled (Pallas kernel)
        "t_collective_s": t_coll,
        "dominant": dominant,
        "model_flops": mf,
        "hlo_flops_global": hlo_global,
        "useful_flops_ratio": mf / hlo_global if hlo_global else 0.0,
        "roofline_bound_s": bound,
        "roofline_fraction": ideal_s / bound if bound else 0.0,
        "roofline_fraction_flash": ideal_s / bound_flash if bound_flash
        else 0.0,
    }


def run_cell(arch: str, shape: str, mesh_kind: str, policy: str,
             out_dir: str, remat: bool = True,
             variant: str = "base") -> Dict[str, Any]:
    import jax
    from repro.launch.mesh import make_production_mesh

    mesh = make_production_mesh(multi_pod=(mesh_kind == "multipod"))
    n_dev = int(mesh.devices.size)
    rec: Dict[str, Any] = {"arch": arch, "shape": shape, "mesh": mesh_kind,
                           "policy": policy, "variant": variant,
                           "n_devices": n_dev}
    t0 = time.perf_counter()
    lowered, cfg, cell = build_lowered(arch, shape, mesh, policy,
                                       remat=remat, variant=variant)
    rec["lower_s"] = time.perf_counter() - t0
    t1 = time.perf_counter()
    compiled = lowered.compile()
    rec["compile_s"] = time.perf_counter() - t1
    rec.update(analyse(lowered, compiled, cfg, cell, n_dev))
    rec["ok"] = True

    os.makedirs(out_dir, exist_ok=True)
    suffix = "" if variant == "base" else f"__{variant}"
    name = f"{arch}__{shape}__{mesh_kind}__{policy}{suffix}.json"
    with open(os.path.join(out_dir, name), "w") as f:
        json.dump(rec, f, indent=1)
    return rec


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--mesh", default="pod",
                    choices=["pod", "multipod", "both"])
    ap.add_argument("--policy", default="baseline")
    ap.add_argument("--variant", default="base",
                    help="'_'-composed knobs from: base bf16score xentchunk "
                         "noremat gqaexpand bf16cast gradbf16 | opt")
    ap.add_argument("--out", default="artifacts/dryrun")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--list", action="store_true")
    ap.add_argument("--no-remat", action="store_true")
    args = ap.parse_args(argv)

    from repro.configs import ARCH_IDS, get_config
    from repro.launch.shapes import cells_for, skipped_cells_for

    if args.list:
        for a in ARCH_IDS:
            cfg = get_config(a)
            print(a, cells_for(cfg),
                  [f"SKIP:{c} ({why[:40]}…)" for c, why in
                   skipped_cells_for(cfg)])
        return 0

    if args.all:
        meshes = (["pod", "multipod"] if args.mesh == "both"
                  else [args.mesh])
        failures = []
        for a in ARCH_IDS:
            for c in cells_for(get_config(a)):
                for mk in meshes:
                    out = os.path.join(
                        args.out, f"{a}__{c}__{mk}__{args.policy}.json")
                    if os.path.exists(out):
                        print(f"[skip cached] {a} {c} {mk}")
                        continue
                    cmd = [sys.executable, "-m", "repro.launch.dryrun",
                           "--arch", a, "--shape", c, "--mesh", mk,
                           "--policy", args.policy, "--out", args.out]
                    print(f"[dryrun] {a} {c} {mk} ...", flush=True)
                    r = subprocess.run(cmd)
                    if r.returncode != 0:
                        failures.append((a, c, mk))
        if failures:
            print("FAILURES:", failures)
            return 1
        print("all cells OK")
        return 0

    assert args.arch and args.shape, "--arch/--shape or --all required"
    meshes = (["pod", "multipod"] if args.mesh == "both" else [args.mesh])
    for mk in meshes:
        rec = run_cell(args.arch, args.shape, mk, args.policy, args.out,
                       remat=not args.no_remat, variant=args.variant)
        print(json.dumps(
            {k: rec[k] for k in ("arch", "shape", "mesh", "variant",
                                 "compile_s", "t_compute_s", "t_memory_s",
                                 "t_memory_flash_s", "t_collective_s",
                                 "dominant", "useful_flops_ratio",
                                 "roofline_fraction")}, indent=1))
        mem = rec.get("memory", {})
        print("memory_analysis:", {k: f"{v/2**30:.2f}GiB"
                                   for k, v in mem.items()
                                   if isinstance(v, float)})
    return 0


if __name__ == "__main__":
    sys.exit(main())
