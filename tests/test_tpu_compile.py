"""Compile the main path for a TPU v5e that is described, not attached.

The TPU compiler refuses what interpret mode accepts: blocks not aligned
to the (8, 128) tiling, too much fast memory, programs that do not fit
the device.  These compiles guard every later chip run at no chip time.
A compile that passes is not a chip run: nothing here executes.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker
imports this file.  Keep these tests in this one file.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, SingleDeviceSharding

V5E_HBM_BYTES = 16 * 10**9          # one TPU v5e chip: 16 GB of HBM


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # an entry written for a described chip cannot be read back here
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def test_flash_attention_compiles_at_qwen_width(one_chip):
    from repro.kernels.flash_attention import flash_attention
    q = _spec(one_chip, (4, 2048, 16, 64), jnp.bfloat16)   # B S H hd
    compiled = _compile(
        lambda q, k, v: flash_attention(q, k, v, causal=True), q, q, q)
    assert "tpu_custom_call" in compiled.as_text()


def test_rmsnorm_compiles_at_qwen_width(one_chip):
    from repro.kernels.rmsnorm import rmsnorm
    compiled = _compile(lambda x, s: rmsnorm(x, s),
                        _spec(one_chip, (4096, 1024), jnp.bfloat16),
                        _spec(one_chip, (1024,), jnp.float32))
    assert "tpu_custom_call" in compiled.as_text()


def test_ssd_scan_compiles_at_mamba2_width(one_chip):
    from repro.kernels.ssd_scan import ssd_scan
    B, S, nh, P, N = 2, 1024, 80, 64, 128          # mamba2-2.7b widths
    f32 = jnp.float32
    compiled = _compile(
        lambda *a: ssd_scan(*a, chunk=128),
        _spec(one_chip, (B, S, nh, P), f32), _spec(one_chip, (B, S, nh), f32),
        _spec(one_chip, (nh,), f32), _spec(one_chip, (B, S, N), f32),
        _spec(one_chip, (B, S, N), f32))
    assert "tpu_custom_call" in compiled.as_text()


def test_qwen_train_step_fits_one_chip(topo, tmp_path):
    """The full-width qwen1.5-0.5b step, as the training launcher builds
    it (bf16 compute, f32 params, AdamW, remat), fits one chip's HBM."""
    from repro.configs import get_config
    from repro.runtime.trainer import TrainConfig, Trainer
    from repro.sharding import get_policy

    mesh = Mesh(np.array(topo.devices[:1]).reshape(1, 1), ("data", "model"))
    tcfg = TrainConfig(batch_size=8, seq_len=512)
    tr = Trainer(get_config("qwen1.5-0.5b"), tcfg, mesh,
                 get_policy("baseline"), str(tmp_path))
    params = jax.tree.map(lambda a, sh: _spec(sh, a.shape, a.dtype),
                          tr.model.init_abstract(),
                          tr.model.param_shardings())
    opt = jax.tree.map(lambda a, sh: _spec(sh, a.shape, a.dtype),
                       tr.opt.init_abstract(params), tr._opt_shardings())
    batch = {"tokens": _spec(SingleDeviceSharding(topo.devices[0]),
                             (tcfg.batch_size, tcfg.seq_len), jnp.int32)}
    with jax.sharding.set_mesh(mesh):
        compiled = tr._step_fn.lower(params, opt, batch).compile()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    assert mem.alias_size_in_bytes > 0            # params/opt donated
    assert total < V5E_HBM_BYTES, total
