"""Elastic restore demo: a unified snapshot taken on an 8-device (4×2)
mesh restored onto a 4-device (2×2) mesh — the scale-down-after-node-loss
path that GPU-side CRIUgpu cannot do (the paper requires identical GPU
count/order; §4.4).

    python examples/elastic_restore.py        # sets its own XLA flags
"""
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import tempfile

import jax
import jax.numpy as jnp
import numpy as np

from repro.api import CheckpointSession
from repro.configs import get_smoke_config
from repro.launch.mesh import make_mesh
from repro.models.encdec import build_model
from repro.optim import AdamW
from repro.optim.schedule import constant
from repro.runtime.elastic import elastic_restore
from repro.sharding import get_policy


def mesh_of(shape):
    return make_mesh(shape, ("data", "model"))


def main():
    cfg = get_smoke_config("qwen1.5-0.5b", d_model=64, num_heads=4,
                           num_kv_heads=4, head_dim=16)
    policy = get_policy("baseline")
    opt = AdamW(lr=constant(1e-3))
    run_dir = tempfile.mkdtemp(prefix="elastic_")

    print(f"devices: {len(jax.devices())}")
    mesh_a = mesh_of((4, 2))
    model_a = build_model(cfg, policy, mesh_a, compute_dtype=jnp.float32,
                          remat=False)
    with jax.sharding.set_mesh(mesh_a):
        params = jax.jit(model_a.init,
                         out_shardings=model_a.param_shardings())(
            jax.random.key(0))
    opt_state = opt.init(params)

    session = CheckpointSession(run_dir, mesh=mesh_a)
    session.attach(lambda: {"train_state": {"params": params,
                                            "opt": opt_state}})
    session.register_host_state("trainer", lambda: {"step": 100},
                                lambda st: None)
    session.register_host_state("data_cursor", lambda: {"step": 100},
                                lambda st: None)
    session.checkpoint(100)
    print("snapshot taken on mesh (4,2): 8 devices")

    print("=== node loss: restore onto mesh (2,2) — 4 devices ===")
    mesh_b = mesh_of((2, 2))
    model_b = build_model(cfg, policy, mesh_b, compute_dtype=jnp.float32,
                          remat=False)
    out = elastic_restore(run_dir, mesh_b, model_b, opt)
    print(f"topology mode: {out['topology_mode']}   step: {out['step']}")

    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(out["params"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    n_dev = {b.sharding.mesh.devices.size
             for b in jax.tree.leaves(out["params"])}
    print(f"restored values bitwise identical; now sharded over {n_dev} "
          f"devices")

    # the restored state trains on the new mesh
    from repro.data import TokenPipeline
    batch = {k: jnp.asarray(v)
             for k, v in TokenPipeline(cfg, 4, 16).next().items()}
    with jax.sharding.set_mesh(mesh_b):
        loss = jax.jit(lambda p, b: model_b.loss(p, b)[0])(out["params"],
                                                           batch)
    print(f"first loss on the replacement mesh: {float(loss):.4f}")
    print("OK")


if __name__ == "__main__":
    main()
