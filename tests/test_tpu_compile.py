"""Compile the main path for a TPU v5e that is described, not attached.

The TPU compiler refuses what interpret mode accepts: blocks not aligned
to the (8, 128) tiling, too much fast memory, programs that do not fit
the device.  These compiles guard every later chip run at no chip time.
A compile that passes is not a chip run: nothing here executes.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker
imports this file.  Keep these tests in this one file.
"""
import dataclasses
import os
import re

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


def _converts_of(hlo_text):
    """(result type, operand type) of every ``convert`` in compiled HLO
    text, which names an operand without its type: look it up."""
    inst = re.compile(r"^\s*(?:ROOT )?%([\w.\-]+) = (\w+\[[\d,]*\])\S* "
                      r"([\w\-]+)\(([^)]*)\)", re.M)
    types, converts = {}, []
    for name, ty, op, args in inst.findall(hlo_text):
        types[name] = ty
        if op == "convert":
            converts.append((ty, args.strip().lstrip("%")))
    return [(ty, types.get(arg)) for ty, arg in converts]


def test_mamba2_decode_step_casts_no_weight(topo, tmp_path):
    """The serve cell's decode step at mamba2-2.7b widths (16 layers,
    B=8, bf16 compute), compiled as the server runs it: on the compute
    copy of the weights it fits one chip and converts no f32 weight.
    The same program on the held f32 tree converts every matrix and the
    embedding each call, which shows the search finds such converts."""
    from repro.configs import get_config
    from repro.runtime.server import DecodeServer
    from repro.sharding import get_policy

    cfg = dataclasses.replace(get_config("mamba2-2.7b"), num_layers=16,
                              vocab_size=50277)
    mesh = Mesh(np.array(topo.devices[:1]).reshape(1, 1), ("data", "model"))
    one = SingleDeviceSharding(topo.devices[0])
    srv = DecodeServer(cfg, get_policy("baseline"), mesh, str(tmp_path),
                       max_seq=513, compute_dtype=jnp.bfloat16)

    def on_chip(tree):
        return jax.tree.map(lambda a: _spec(one, a.shape, a.dtype), tree)

    held = on_chip(srv.model.init_abstract())
    weights = {f"f32{list(a.shape)}".replace(" ", "")
               for a in jax.tree.leaves(held) if len(a.shape) >= 2}
    cache = on_chip(srv.model.cache_abstract(8, 513))
    tok, pos = _spec(one, (8,), jnp.int32), _spec(one, (), jnp.int32)

    def compile_step(params):
        with jax.sharding.set_mesh(mesh):
            return srv._decode.lower(params, cache, tok, pos).compile()

    def weight_converts(compiled):
        return [c for c in _converts_of(compiled.as_text())
                if c[1] in weights]

    assert len(weight_converts(compile_step(held))) >= 4
    compiled = compile_step(on_chip(
        jax.eval_shape(srv.model.compute_params, held)))
    assert _converts_of(compiled.as_text())        # the search ran
    assert weight_converts(compiled) == []
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    assert total < V5E_HBM_BYTES, total


def _granite_cut():
    """The benchmark's cut of granite-4.0-h-small: one period of ten
    layers, 9 of the 72 experts held, an eighth of the vocabulary."""
    from repro.configs import get_config
    return dataclasses.replace(get_config("granite-4.0-h-small"),
                               num_layers=10, moe_experts_held=9,
                               vocab_size=12544)


@pytest.fixture(scope="module")
def granite_server(topo, tmp_path_factory):
    """The serve cell's replica on one described chip: bf16 weights held
    and computed on, B=8, max_seq 16,384."""
    from repro.runtime.server import DecodeServer
    from repro.sharding import get_policy
    mesh = Mesh(np.array(topo.devices[:1]).reshape(1, 1), ("data", "model"))
    srv = DecodeServer(_granite_cut(), get_policy("baseline"), mesh,
                       str(tmp_path_factory.mktemp("granite")),
                       max_seq=16384, compute_dtype=jnp.bfloat16,
                       param_dtype=jnp.bfloat16)
    one = SingleDeviceSharding(topo.devices[0])
    held = jax.tree.map(lambda a: _spec(one, a.shape, a.dtype),
                        srv.model.init_abstract())
    return srv, mesh, one, held


@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_granite_cut_fits_one_chip_and_casts_no_weight(granite_server,
                                                        program):
    """The granite cut's decode step at B=8 over a 16,384-row cache, and
    its prefill of 8 prompts of 2,048 tokens, as the server runs them:
    each fits one chip, and neither converts a weight it reads in the
    compute dtype (the server holds bf16 and computes in bf16, so it
    builds no compute copy).  Norm gains, conv taps and the SSM terms
    are read in f32 by design: a few KB a layer."""
    from repro.models.layers import ParamSpec
    srv, mesh, one, held = granite_server
    assert srv.model.compute_params(held) is held
    weights = {f"bf16{list(s.shape)}".replace(" ", "")
               for s in jax.tree.leaves(
                   srv.model._specs,
                   is_leaf=lambda x: isinstance(x, ParamSpec)) if s.cast}
    with jax.sharding.set_mesh(mesh):
        if program == "decode":
            cache = jax.tree.map(lambda a: _spec(one, a.shape, a.dtype),
                                 srv.model.cache_abstract(8, 16384))
            compiled = srv._decode.lower(
                held, cache, _spec(one, (8,), jnp.int32),
                _spec(one, (), jnp.int32)).compile()
        else:
            compiled = srv._prefill.lower(
                held, {"tokens": _spec(one, (8, 2048), jnp.int32)}).compile()
    converts = _converts_of(compiled.as_text())
    assert converts                                 # the search ran
    assert [c for c in converts if c[1] in weights] == []
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    assert total < V5E_HBM_BYTES, total
