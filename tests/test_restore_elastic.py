"""Elastic / cross-topology restore (the GPUID-translation analogue, taken
further: restore onto a different device count — paper §3.1.2 / §4.4).

The multi-device cases run in a subprocess with 8 host devices so the main
test process keeps its single-device view (per the dry-run isolation rule).
"""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import SnapshotEngine

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_single_device_topology_mode_identical(tmp_path, mesh1):
    from jax.sharding import NamedSharding, PartitionSpec as P
    state = {"w": jax.device_put(jnp.arange(16.0).reshape(4, 4),
                                 NamedSharding(mesh1, P("data")))}
    eng = SnapshotEngine(str(tmp_path), mesh=mesh1)
    eng.attach(lambda: {"train_state": state})
    eng.checkpoint(1)
    eng2 = SnapshotEngine(str(tmp_path), mesh=mesh1)
    eng2.attach(lambda: {"train_state": None})
    restored = eng2.restore(mesh=mesh1)
    assert eng2.last_stats["topology_mode"] == "identical"
    np.testing.assert_array_equal(np.asarray(restored["train_state"]["w"]),
                                  np.asarray(state["w"]))


_ELASTIC_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.launch.mesh import make_mesh
    from repro.configs import get_smoke_config
    from repro.optim import AdamW
    from repro.optim.schedule import constant
    from repro.models.encdec import build_model
    from repro.runtime.elastic import elastic_restore
    from repro.sharding import get_policy
    from repro.core import SnapshotEngine

    run_dir = os.environ["RUN_DIR"]
    cfg = get_smoke_config("qwen1.5-0.5b", d_model=64, num_heads=4,
                           num_kv_heads=4, head_dim=16)
    policy = get_policy("baseline")
    opt = AdamW(lr=constant(1e-3))

    def build(mesh):
        model = build_model(cfg, policy, mesh, compute_dtype=jnp.float32,
                            remat=False)
        return model

    mesh_a = make_mesh((4, 2), ("data", "model"))
    model_a = build(mesh_a)
    with jax.sharding.set_mesh(mesh_a):
        params = jax.jit(model_a.init,
                         out_shardings=model_a.param_shardings())(
            jax.random.key(0))
        opt_state = opt.init(params)
    engine = SnapshotEngine(run_dir, mesh=mesh_a)
    engine.attach(lambda: {"train_state": {"params": params,
                                           "opt": opt_state}})
    engine.register_host_state("trainer", lambda: {"step": 3},
                               lambda st: None)
    engine.register_host_state("data_cursor", lambda: {"step": 3},
                               lambda st: None)
    engine.checkpoint(3)

    # ---- restore onto a *smaller* mesh (scale-down after node loss) ----
    mesh_b = make_mesh((2, 2), ("data", "model"))
    model_b = build(mesh_b)
    out = elastic_restore(run_dir, mesh_b, model_b, opt)
    assert out["topology_mode"] == "resharded", out["topology_mode"]
    assert out["step"] == 3

    ref = jax.tree.leaves(params)
    got = jax.tree.leaves(out["params"])
    for a, b in zip(ref, got):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert b.sharding.mesh.devices.size == 4       # lives on mesh_b

    # restored state is *usable*: run a step on the new mesh
    from repro.data import TokenPipeline
    batch = {k: jnp.asarray(v)
             for k, v in TokenPipeline(cfg, 4, 16).next().items()}
    def loss_fn(p, b):
        return model_b.loss(p, b)[0]
    with jax.sharding.set_mesh(mesh_b):
        loss, grads = jax.jit(jax.value_and_grad(loss_fn))(out["params"],
                                                           batch)
    assert np.isfinite(float(loss))

    # ---- identical-mesh restore keeps 1:1 shard placement -------------
    model_a2 = build(mesh_a)
    out2 = elastic_restore(run_dir, mesh_a, model_a2, opt)
    assert out2["topology_mode"] == "identical", out2["topology_mode"]
    for a, b in zip(ref, jax.tree.leaves(out2["params"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    print("ELASTIC_OK")
""")


@pytest.mark.slow
def test_elastic_restore_across_meshes(tmp_path):
    env = dict(os.environ, RUN_DIR=str(tmp_path / "run"),
               PYTHONPATH=os.path.join(REPO, "src"), JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", _ELASTIC_SCRIPT],
                       capture_output=True, text=True, env=env,
                       timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "ELASTIC_OK" in r.stdout
