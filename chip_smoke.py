#!/usr/bin/env python3
"""Run the main path once on a TPU, at the full width of qwen1.5-0.5b.

    python chip_smoke.py              # one chip: train, serve, kernels
    python chip_smoke.py --chips 4    # four chips: the sharded path only

Everything runs in this one process: a chip belongs to one process at a
time, so nothing here starts a child that uses JAX.

One chip, three phases:

  train    the training launcher (``repro.launch.train.main``) runs the
           full config in bf16 for a few steps, saves once (async),
           crashes at ``--fail-at`` and resumes with ``--restore`` from the
           newest valid image.  The final loss must be bit-identical to an
           uninterrupted run of the same length, and ``repro verify`` must
           pass on the image.
  serve    a ``DecodeServer`` decodes, snapshots mid-generation, and a
           fresh server is cold-restored from the image.  The tokens it
           goes on to generate must equal an uninterrupted generation's.
  kernels  flash attention, RMSNorm and the SSD scan run compiled
           (``interpret=False``) and must match ``repro.kernels.ref``.

Four chips, one phase: sharded training on a (2, 2) ("data", "model")
mesh with one save and a crash; a resume on (2, 2) that must be bit-exact
against the uninterrupted (2, 2) run; a restore onto (4, 1) whose params
must equal the saved ones bitwise and whose losses must stay within
``LOSS_RTOL`` of the (2, 2) run.  No device may hold the whole model.

Each phase prints one JSON line; its wall times are smoke timings, not
benchmark numbers.  The last line, ``{"ok": true, "device": {...}}``, is
printed only when every phase passed on a TPU: without one, or when a
phase fails, the script exits non-zero without it.  Run directories go
under ``runs/chip_smoke`` (gitignored).
"""
from __future__ import annotations

import argparse
import contextlib
import gc
import io
import json
import pathlib
import re
import shutil
import sys
import time
import traceback

ROOT = pathlib.Path(__file__).resolve().parent
RUNS = ROOT / "runs" / "chip_smoke"
ARCH = "qwen1.5-0.5b"
SEED = 0

# training: the step count stays below StragglerMonitor.min_samples, so
# no just-in-time save joins the one periodic save
TRAIN = dict(batch=8, seq=512, steps=6, ckpt_every=4, fail_at=5)
SERVE = dict(batch=4, prompt=16, max_seq=128, snapshot_at=8, tokens=24)
# qwen1.5-0.5b attention (B, S, H, hd); RMSNorm (rows, d_model);
# mamba2-2.7b SSD (B, S, heads, head dim, state) at its chunk of 128
KERNELS = dict(attn=(4, 2048, 16, 64), norm=(4096, 1024),
               ssd=(2, 1024, 80, 64, 128), chunk=128)
# max |kernel - ref| / max |ref|, ref at "highest" matmul precision.  The
# kernels round through bf16 (attention and RMSNorm outputs, MXU passes),
# about 2**-8 relative; a wrong mask, tile or carried state is O(1).
KERNEL_TOL = 2e-2
# (4, 1) vs (2, 2): the same math in another reduction order, in bf16
LOSS_RTOL = 1e-2


class PhaseFailed(AssertionError):
    pass


def _check(cond, msg: str) -> None:
    if not cond:
        raise PhaseFailed(msg)


def _launch(main, argv):
    """Run a launcher's ``main`` in-process; (exit code, stdout)."""
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        rc = main([str(a) for a in argv])
    gc.collect()           # a dropped Trainer is cyclic: free its arrays now
    return rc, buf.getvalue()


def _image_bytes(run_dir, step: int) -> int:
    from repro.core.snapshot_io import snapshot_dir
    return sum(f.stat().st_size
               for f in pathlib.Path(snapshot_dir(str(run_dir), step)).rglob("*")
               if f.is_file())


# ------------------------------------------------------------------ train
def train_phase(root, *, smoke: bool = False, batch: int = TRAIN["batch"],
                seq: int = TRAIN["seq"], steps: int = TRAIN["steps"],
                ckpt_every: int = TRAIN["ckpt_every"],
                fail_at: int = TRAIN["fail_at"]) -> dict:
    """Train, save, crash, resume through ``repro.launch.train.main``."""
    from repro.cli import main as repro_cli
    from repro.launch.train import main as train_main

    root = pathlib.Path(root)
    common = ["--arch", ARCH, "--batch-size", batch, "--seq-len", seq,
              "--steps", steps, "--seed", SEED] + (["--smoke"] if smoke
                                                   else [])
    t = {}
    t0 = time.perf_counter()
    rc, out = _launch(train_main, common + ["--ckpt-every", 0, "--run-dir",
                                            root / "train_ref"])
    _check(rc == 0, f"uninterrupted run exited {rc}")
    ref = json.loads(out[out.index("{"):])
    t["uninterrupted_s"] = time.perf_counter() - t0

    run_dir = root / "train"
    kill = common + ["--ckpt-every", ckpt_every, "--run-dir", run_dir]
    t0 = time.perf_counter()
    rc, _ = _launch(train_main, kill + ["--fail-at", fail_at])
    _check(rc == 1, f"run with --fail-at {fail_at} exited {rc}, not 1")
    t["save_and_crash_s"] = time.perf_counter() - t0

    t0 = time.perf_counter()
    rc, out = _launch(train_main, kill + ["--restore"])
    _check(rc == 0, f"resumed run exited {rc}")
    restored = re.search(r"restored unified snapshot at step (\d+)", out)
    _check(restored is not None, "--restore did not restore an image")
    restored_step = int(restored.group(1))
    resumed = json.loads(out[out.index("{"):])
    t["resume_s"] = time.perf_counter() - t0

    t0 = time.perf_counter()
    rc, out = _launch(repro_cli, ["verify", run_dir])
    _check(rc == 0, f"repro verify failed: {out.strip()}")
    t["verify_s"] = time.perf_counter() - t0

    _check(restored_step == ckpt_every,
           f"resumed from step {restored_step}, saved at {ckpt_every}")
    _check(resumed["steps"] == ref["steps"] == steps,
           f"steps {resumed['steps']} / {ref['steps']}, wanted {steps}")
    _check(resumed["final_loss"] == ref["final_loss"],
           f"resumed loss {resumed['final_loss']!r} != uninterrupted "
           f"{ref['final_loss']!r}")
    return {"final_loss": ref["final_loss"],
            "resumed_final_loss": resumed["final_loss"],
            "restored_step": restored_step,
            "image_bytes": _image_bytes(run_dir, restored_step),
            "smoke_times_s": t}


# ------------------------------------------------------------------ serve
def serve_phase(root, *, smoke: bool = False, batch: int = SERVE["batch"],
                prompt: int = SERVE["prompt"],
                max_seq: int = SERVE["max_seq"],
                snapshot_at: int = SERVE["snapshot_at"],
                tokens: int = SERVE["tokens"]) -> dict:
    """Decode, snapshot mid-generation, cold-restore a fresh server."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import get_config, get_smoke_config
    from repro.data import TokenPipeline
    from repro.launch.mesh import make_host_mesh
    from repro.runtime.server import DecodeServer
    from repro.sharding import get_policy

    cfg = get_smoke_config(ARCH) if smoke else get_config(ARCH)
    dtype = jnp.float32 if smoke else jnp.bfloat16
    mesh = make_host_mesh(data=len(jax.devices()), model=1)
    policy = get_policy("baseline")
    run_dir = str(pathlib.Path(root) / "serve")
    t = {}

    t0 = time.perf_counter()
    srv = DecodeServer(cfg, policy, mesh, run_dir, max_seq=max_seq,
                       compute_dtype=dtype)
    srv.load(srv.model.init(jax.random.key(SEED)))
    srv.start(TokenPipeline(cfg, batch, prompt, seed=SEED).next())
    srv.decode(snapshot_at)
    snap_pos = srv.pos
    t["prefill_and_decode_s"] = time.perf_counter() - t0
    t0 = time.perf_counter()
    srv.checkpoint(0)
    srv.session.wait_pending()
    t["snapshot_s"] = time.perf_counter() - t0
    srv.decode(tokens - snapshot_at)
    want = np.array(srv.tokens)
    model = srv.model
    del srv
    gc.collect()

    t0 = time.perf_counter()
    cold = DecodeServer(cfg, policy, mesh, run_dir, max_seq=max_seq,
                        compute_dtype=dtype, model=model)
    pos = cold.restore()
    t["cold_restore_s"] = time.perf_counter() - t0
    _check(pos == snap_pos, f"restored at pos {pos}, snapshot at {snap_pos}")
    cold.decode(tokens - snapshot_at)
    got = np.array(cold.tokens)
    _check(got.shape == want.shape == (batch, prompt + 1 + tokens),
           f"token shapes {got.shape} / {want.shape}")
    _check(np.array_equal(got, want),
           f"resumed tokens differ at {np.argwhere(got != want)[:4].tolist()}")
    return {"generated": int(want.shape[1] - prompt),
            "snapshot_pos": snap_pos, "image_bytes": _image_bytes(run_dir, 0),
            "smoke_times_s": t}


# ---------------------------------------------------------------- kernels
def _rel_err(got, want) -> float:
    import numpy as np
    g = np.asarray(got, np.float32)
    w = np.asarray(want, np.float32)
    _check(g.shape == w.shape, f"shape {g.shape} != {w.shape}")
    _check(np.isfinite(g).all(), "non-finite kernel output")
    return float(np.max(np.abs(g - w)) / max(float(np.max(np.abs(w))),
                                              1e-30))


def kernel_phase(shapes=None, *, interpret: bool = False) -> dict:
    """Each Pallas kernel at real widths against its jnp oracle."""
    import functools

    import jax
    import jax.numpy as jnp

    from repro.kernels import ref
    from repro.kernels.flash_attention import flash_attention
    from repro.kernels.rmsnorm import rmsnorm
    from repro.kernels.ssd_scan import ssd_scan

    shapes = shapes or KERNELS
    ks = iter(jax.random.split(jax.random.key(SEED), 16))
    normal = lambda shp, dt=jnp.float32: jax.random.normal(next(ks), shp, dt)

    B, S, H, hd = shapes["attn"]
    qkv = [normal((B, S, H, hd), jnp.bfloat16) for _ in range(3)]
    rows, d = shapes["norm"]
    norm_args = (normal((rows, d), jnp.bfloat16), 1.0 + 0.1 * normal((d,)))
    Bs, Ss, nh, P, N = shapes["ssd"]
    ssd_args = (normal((Bs, Ss, nh, P)),
                jax.nn.softplus(normal((Bs, Ss, nh)) - 1.0),
                -jnp.exp(0.5 * normal((nh,))),
                normal((Bs, Ss, N)) / N ** 0.5,
                normal((Bs, Ss, N)) / N ** 0.5)

    cases = {
        "flash_attention": (
            functools.partial(flash_attention, causal=True,
                              interpret=interpret),
            functools.partial(ref.attention_ref, causal=True), qkv),
        "rmsnorm": (functools.partial(rmsnorm, interpret=interpret),
                    ref.rmsnorm_ref, norm_args),
        "ssd_scan": (functools.partial(ssd_scan, chunk=shapes["chunk"],
                                       interpret=interpret),
                     ref.ssd_ref, ssd_args),
    }
    out = {}
    for name, (kernel, oracle, args) in cases.items():
        fn = jax.jit(kernel)
        if not interpret:
            _check("tpu_custom_call" in fn.lower(*args).as_text(),
                   f"{name} did not lower to a Mosaic kernel")
        t0 = time.perf_counter()
        got = jax.block_until_ready(fn(*args))
        wall = time.perf_counter() - t0
        with jax.default_matmul_precision("highest"):
            want = jax.jit(oracle)(*args)
        got, want = jax.tree.leaves(got), jax.tree.leaves(want)
        err = max(_rel_err(g, w) for g, w in zip(got, want))
        _check(err <= KERNEL_TOL, f"{name}: error {err} > {KERNEL_TOL}")
        out[name] = {"rel_err": err, "smoke_first_call_s": wall}
    return {"kernels": out, "interpret": interpret, "tol": KERNEL_TOL}


# -------------------------------------------------------------- four chips
def _param_bytes_per_device(params) -> dict:
    import jax
    per = {}
    for leaf in jax.tree.leaves(params):
        for sh in leaf.addressable_shards:
            per[sh.device.id] = per.get(sh.device.id, 0) + sh.data.nbytes
    return per


def _bytes_in_use() -> dict:
    import jax
    return {d.id: (d.memory_stats() or {}).get("bytes_in_use")
            for d in jax.devices()}


def multichip_phase(root, *, smoke: bool = False,
                    batch: int = TRAIN["batch"], seq: int = TRAIN["seq"],
                    steps: int = TRAIN["steps"],
                    ckpt_every: int = TRAIN["ckpt_every"],
                    fail_at: int = TRAIN["fail_at"]) -> dict:
    """Sharded train/save/crash/resume on (2, 2), restore onto (4, 1).

    The Trainer is built as ``repro.launch.train`` builds it, on the
    meshes this phase needs."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding

    from repro.api import CheckpointOptions
    from repro.configs import get_config, get_smoke_config
    from repro.launch.mesh import make_mesh
    from repro.runtime.trainer import SimulatedFailure, TrainConfig, Trainer
    from repro.sharding import get_policy

    _check(len(jax.devices()) >= 4,
           f"needs 4 devices, JAX has {len(jax.devices())}")
    cfg = get_smoke_config(ARCH) if smoke else get_config(ARCH)
    policy = get_policy("baseline")
    root = pathlib.Path(root)
    mesh22 = make_mesh((2, 2), ("data", "model"))
    mesh41 = make_mesh((4, 1), ("data", "model"))

    def trainer(mesh, run_dir, every):
        tcfg = TrainConfig(
            batch_size=batch, seq_len=seq, total_steps=steps,
            ckpt_every=every, ckpt=CheckpointOptions(mode="async", keep=3),
            seed=SEED,
            compute_dtype=jnp.float32 if smoke else jnp.bfloat16)
        return Trainer(cfg, tcfg, mesh, policy, str(run_dir))

    def placement(tr, mesh):
        """Every train-state leaf on `mesh`, none holding the whole model."""
        state = (tr.params, tr.opt_state)
        off = [leaf.sharding for leaf in jax.tree.leaves(state)
               if not (isinstance(leaf.sharding, NamedSharding)
                       and leaf.sharding.mesh == mesh)]
        _check(not off, f"{len(off)} leaves off the mesh, e.g. {off[:1]}")
        per = _param_bytes_per_device(tr.params)
        whole = sum(leaf.nbytes for leaf in jax.tree.leaves(tr.params))
        _check(max(per.values()) < whole,
               f"a device holds the whole model: {per} of {whole}")
        return {"param_bytes_per_device": per, "param_bytes": whole}

    def host_params(tr):
        return [np.asarray(jax.device_get(x))
                for x in jax.tree.leaves(tr.params)]

    out, t = {}, {}
    t0 = time.perf_counter()
    tr = trainer(mesh22, root / "mc_ref", 0)
    tr.initialize()
    out["initialized_2x2"] = placement(tr, mesh22)
    tr.run(steps)
    ref_losses = list(tr.metrics_history["loss"])
    out["bytes_in_use_2x2"] = _bytes_in_use()
    del tr
    gc.collect()
    t["uninterrupted_2x2_s"] = time.perf_counter() - t0

    run_dir = root / "mc"
    t0 = time.perf_counter()
    tr = trainer(mesh22, run_dir, ckpt_every)
    tr.initialize()
    try:
        tr.run(steps, fail_at=fail_at)
        raise PhaseFailed(f"no crash at step {fail_at}")
    except SimulatedFailure:
        tr.session.wait_pending()
    del tr
    gc.collect()
    t["save_and_crash_2x2_s"] = time.perf_counter() - t0

    t0 = time.perf_counter()
    tr = trainer(mesh22, run_dir, ckpt_every)
    step = tr.restore()
    _check(step == ckpt_every, f"resumed from {step}, saved at {ckpt_every}")
    placement(tr, mesh22)
    saved = host_params(tr)
    tr.run(steps - step)
    resumed = list(tr.metrics_history["loss"])
    _check(resumed == ref_losses,
           f"(2,2) resume {resumed} != uninterrupted {ref_losses}")
    del tr
    gc.collect()
    t["resume_2x2_s"] = time.perf_counter() - t0

    t0 = time.perf_counter()
    tr = trainer(mesh41, run_dir, ckpt_every)
    step = tr.restore()
    _check(step == ckpt_every, f"(4,1) restored {step}, saved {ckpt_every}")
    out["restored_4x1"] = placement(tr, mesh41)
    moved = host_params(tr)
    _check(len(moved) == len(saved)
           and all(np.array_equal(a, b) for a, b in zip(saved, moved)),
           "params restored onto (4,1) differ from the saved ones")
    tr.run(steps - step)
    relaid = tr.metrics_history["loss"][step:]
    _check(np.allclose(relaid, ref_losses[step:], rtol=LOSS_RTOL, atol=0),
           f"(4,1) losses {relaid} vs (2,2) {ref_losses[step:]}")
    out["bytes_in_use_4x1"] = _bytes_in_use()
    del tr
    gc.collect()
    t["restore_4x1_s"] = time.perf_counter() - t0

    out.update(losses_2x2=ref_losses, losses_4x1=relaid,
               image_bytes=_image_bytes(run_dir, ckpt_every),
               smoke_times_s=t)
    return out


# ------------------------------------------------------------------- main
def _memory() -> dict:
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    return {k: stats.get(k) for k in ("peak_bytes_in_use", "bytes_in_use")}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded four-chip phase")
    args = ap.parse_args(argv)

    # the program first: a copy of this file alone has nothing to run
    from repro.launch.compile_cache import use_compile_cache
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {platform!r}); "
              f"this script has no CPU fallback", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX has "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 1
    print(json.dumps({"device_kind": devices[0].device_kind,
                      "count": len(devices),
                      "compile_cache": use_compile_cache()}), flush=True)
    shutil.rmtree(RUNS, ignore_errors=True)
    if args.chips == 4:
        phases = {"multichip": lambda: multichip_phase(RUNS)}
    else:
        phases = {"train": lambda: train_phase(RUNS),
                  "serve": lambda: serve_phase(RUNS),
                  "kernels": kernel_phase}
    ok = True
    for name, phase in phases.items():
        t0 = time.perf_counter()
        try:
            rec = {"phase": name, "ok": True, **phase()}
        except Exception as e:               # report it, run the next phase
            traceback.print_exc()
            rec = {"phase": name, "ok": False, "error": repr(e)}
            ok = False
        gc.collect()
        rec.update(smoke_wall_s=time.perf_counter() - t0, **_memory())
        print(json.dumps(rec, default=str), flush=True)
    if not ok:
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.path.insert(0, str(ROOT / "src"))
    sys.exit(main())
