"""Decode-server serving-state snapshots: checkpoint a half-finished
generation, restore into a fresh server, continue token-exact (the paper's
inference-side story — Modal/MemVerge cold-start snapshots)."""
import functools
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.runtime.server import DecodeServer
from repro.sharding import get_policy

POLICY = get_policy("baseline")


def make_server(arch, run_dir, mesh):
    cfg = get_smoke_config(arch)
    srv = DecodeServer(cfg, POLICY, mesh, run_dir, max_seq=64)
    from repro.models.encdec import build_model
    model = build_model(cfg, POLICY, mesh, compute_dtype=jnp.float32,
                        remat=False)
    srv.load(model.init(jax.random.key(0)))
    return srv, cfg


def _prompt(cfg, B=2, S=12):
    from repro.data import TokenPipeline
    return TokenPipeline(cfg, B, S, seed=9).next()


@pytest.mark.parametrize("arch", ["qwen1.5-0.5b", "mamba2-2.7b"])
def test_snapshot_mid_generation_token_exact(arch, tmp_path, mesh1):
    run = str(tmp_path / "srv")
    srv, cfg = make_server(arch, run, mesh1)
    batch = _prompt(cfg)
    srv.start(batch)
    srv.decode(3)
    srv.checkpoint(0)
    expected = srv.decode(4).copy()        # uninterrupted continuation

    srv2, _ = make_server(arch, run, mesh1)
    srv2.start(batch)                       # warm structures, then restore
    srv2.restore()
    assert srv2.pos == srv.pos - 4
    got = srv2.decode(4)
    np.testing.assert_array_equal(expected, got)


@pytest.mark.parametrize("arch", ["qwen1.5-0.5b", "mamba2-2.7b"])
def test_cold_boot_restore_token_exact(arch, tmp_path, mesh1):
    """A *fresh* server — nothing loaded, never started — restores
    straight from the image: the decode cursor sizes an abstract cache
    skeleton, no prefill re-execution (the fleet fan-out path)."""
    run = str(tmp_path / "srv")
    srv, cfg = make_server(arch, run, mesh1)
    batch = _prompt(cfg)
    srv.start(batch)
    srv.decode(3)
    srv.checkpoint(0)
    expected = srv.decode(4).copy()

    srv2 = DecodeServer(get_smoke_config(arch), POLICY, mesh1, run,
                        max_seq=64)
    srv2.restore()                          # cold: no start(), no load()
    assert srv2.pos == srv.pos - 4
    got = srv2.decode(4)
    np.testing.assert_array_equal(expected, got)


def test_cold_boot_restore_lazy_token_exact(tmp_path, mesh1):
    """Cold boot under lazy restore: params place first, the cache
    skeleton is abstract until the first decode joins the stream."""
    from repro.api import CheckpointOptions
    run = str(tmp_path / "srv")
    srv, cfg = make_server("qwen1.5-0.5b", run, mesh1)
    batch = _prompt(cfg)
    srv.start(batch)
    srv.decode(3)
    srv.checkpoint(0)
    expected = srv.decode(4).copy()

    srv2 = DecodeServer(cfg, POLICY, mesh1, run, max_seq=64,
                        options=CheckpointOptions(restore_mode="lazy"))
    srv2.restore()
    assert srv2.params is not None          # critical set placed
    got = srv2.decode(4)                    # first decode joins the stream
    np.testing.assert_array_equal(expected, got)
    assert not srv2.session.lazy_pending


def test_greedy_decode_matches_model_argmax(tmp_path, mesh1):
    srv, cfg = make_server("qwen1.5-0.5b", str(tmp_path / "s"), mesh1)
    batch = _prompt(cfg, B=1, S=8)
    srv.start(batch)
    toks = srv.decode(2)
    assert toks.shape == (1, 8 + 1 + 2)
    assert int(toks.max()) < cfg.vocab_size    # padded vocab never sampled


# ------------------------------------------- the compute copy of the weights
def _drive_model(srv, params, batch, n):
    """Greedy decode straight through the server's model, f32 params in:
    what a server that casts nothing ahead of time computes."""
    model = srv.model
    logits, cache = jax.jit(model.prefill)(
        params, {k: jnp.asarray(v) for k, v in batch.items()})
    cache = srv._pad_cache(cache, srv.max_seq)
    S = batch["tokens"].shape[1]
    toks = [np.asarray(jnp.argmax(logits, axis=-1), np.int32)]
    step = jax.jit(model.decode_step)
    for i in range(n):
        logits, cache = step(params, cache, jnp.asarray(toks[-1]),
                             jnp.int32(S + i))
        toks.append(np.asarray(jnp.argmax(logits, axis=-1), np.int32))
    return np.stack(toks, axis=1), cache


def _leaves_equal(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    return len(la) == len(lb) and all(
        x.dtype == y.dtype and np.array_equal(np.asarray(x), np.asarray(y))
        for x, y in zip(la, lb))


@pytest.mark.parametrize("arch", ["mamba2-2.7b", "qwen1.5-0.5b",
                                  "qwen3-moe-30b-a3b"])
def test_bf16_compute_copy_is_bitwise_exact(arch, tmp_path):
    """A bf16-compute server decodes from a bf16 copy of its matrices,
    biases and embedding (norm scales, SSM terms, conv taps and the MoE
    router stay f32): its tokens and cache are bit for bit those of the
    model's programs fed the f32 params, and its image holds the f32
    params it loaded.  With f32 compute the copy is the params tree."""
    from repro.launch.mesh import make_host_mesh
    mesh = make_host_mesh(1, 1)
    cfg = get_smoke_config(arch)
    run = str(tmp_path / "srv")
    srv = DecodeServer(cfg, POLICY, mesh, run, max_seq=32,
                       compute_dtype=jnp.bfloat16)
    # every leaf off the bf16 grid: init's zeros and ones (norm scales,
    # A_log, dt_bias, D) would hide a leaf wrongly cast
    params = srv.model.init(jax.random.key(1))
    noise = jax.random.split(jax.random.key(3), len(jax.tree.leaves(params)))
    params = jax.tree.unflatten(jax.tree.structure(params), [
        p + 0.01 * jax.random.normal(k, p.shape, p.dtype)
        for p, k in zip(jax.tree.leaves(params), noise)])
    srv.load(params)
    batch = _prompt(cfg, B=2, S=8)
    srv.start(batch)
    srv.decode(5)
    want_tokens, want_cache = _drive_model(srv, params, batch, 5)
    np.testing.assert_array_equal(srv.tokens[:, 8:], want_tokens)
    assert _leaves_equal(srv.cache, want_cache)

    held = jax.tree.leaves(params)
    copy = jax.tree.leaves(srv._compute_params())
    assert {str(p.dtype) for p in copy} == {"float32", "bfloat16"}
    assert any(c is p for c, p in zip(copy, held))     # f32 leaves shared

    srv.checkpoint(0)
    cold = DecodeServer(cfg, POLICY, mesh, run, max_seq=32,
                        model=srv.model)
    cold.restore()
    assert {str(p.dtype) for p in jax.tree.leaves(cold.params)} == \
        {"float32"}
    assert _leaves_equal(cold.params, params)

    f32 = DecodeServer(cfg, POLICY, mesh, str(tmp_path / "f32"),
                       max_seq=32)
    f32.load(params)
    assert f32._compute_params() is f32.params


@pytest.mark.parametrize("path", ["eager", "cold", "lazy"])
def test_restore_rebuilds_the_compute_copy(path, tmp_path):
    """Weights A loaded and decoded, then an image taken with weights B
    restored into the same server: decoding follows B, so the compute
    copy was rebuilt and not left stale.  ``serve.weights_cast`` counts
    one copy per load or restore and none per token."""
    from repro.api import CheckpointOptions
    from repro.launch.mesh import make_host_mesh
    from repro.obs import metrics
    mesh = make_host_mesh(1, 1)
    cfg = get_smoke_config("mamba2-2.7b")
    run = str(tmp_path / "srv")
    writer = DecodeServer(cfg, POLICY, mesh, run, max_seq=32,
                          compute_dtype=jnp.bfloat16)
    weights_b = writer.model.init(jax.random.key(2))
    writer.load(weights_b)
    batch = _prompt(cfg, B=2, S=8)
    writer.start(batch)
    writer.decode(3)
    writer.checkpoint(0)
    expected = writer.decode(4).copy()

    options = (CheckpointOptions(restore_mode="lazy") if path == "lazy"
               else None)
    srv = DecodeServer(cfg, POLICY, mesh, run, max_seq=32,
                       model=writer.model, options=options)
    reg = metrics.MetricsRegistry()
    metrics.install(reg)
    try:
        def casts():
            return reg.counters.get("serve.weights_cast", 0)

        srv.load(writer.model.init(jax.random.key(1)))       # weights A
        srv.start(batch)
        assert casts() == 1
        srv.decode(5)
        assert casts() == 1                                  # none a token
        if path == "cold":
            srv.cache = None          # no live cache: the cold branch
        srv.restore()
        assert srv.pos == writer.pos - 4
        got = srv.decode(4)
        assert casts() == 2
    finally:
        metrics.uninstall()
    np.testing.assert_array_equal(expected, got)
    assert _leaves_equal(srv.params, weights_b)


# ------------------------------------------------- the pipelined decode loop
@functools.lru_cache(maxsize=None)
def _shared(arch):
    """One mesh and model per architecture, so the chunk sizes share its
    compiled programs."""
    from repro.launch.mesh import make_host_mesh
    from repro.models.encdec import build_model
    mesh = make_host_mesh(1, 1)
    return mesh, build_model(get_smoke_config(arch), POLICY, mesh,
                             compute_dtype=jnp.float32, remat=False)


@pytest.mark.parametrize("chunk", [1, 2, 5])     # below, at, above _LAG
@pytest.mark.parametrize("arch", ["mamba2-2.7b", "qwen1.5-0.5b",
                                  "granite-4.0-h-small"])
def test_pipelined_decode_matches_step_by_step(arch, chunk, tmp_path):
    """decode_until fetches tokens behind the device, yet gives the tokens
    and the final cache of the step-by-step reference bit for bit, in
    calls of any length.  A preemption mid-call and an injected failure
    both leave the history caught up with ``pos``, and the preemption's
    snapshot resumes token-exact in a fresh server."""
    from repro.runtime.trainer import SimulatedFailure
    mesh, model = _shared(arch)
    cfg = get_smoke_config(arch)
    run = str(tmp_path / "srv")
    srv = DecodeServer(cfg, POLICY, mesh, run, max_seq=64, model=model)
    params = srv.model.init(jax.random.key(0))
    srv.load(params)
    batch = _prompt(cfg, B=2, S=8)
    n = 6 + 2 * chunk
    want_tokens, want_cache = _drive_model(srv, params, batch, n)
    want = np.concatenate([np.asarray(batch["tokens"], np.int32),
                           want_tokens], axis=1)

    def caught_up():
        assert srv.tokens.shape[1] == srv.pos + 1
        np.testing.assert_array_equal(srv.tokens, want[:, :srv.pos + 1])

    srv.start(batch)
    while srv.pos < 8 + 3:
        srv.decode_until(srv.pos + chunk)
        caught_up()

    polls = []                         # fires once `chunk` steps are out
    out = srv.decode_until(srv.pos + chunk + 2,
                           preempt=lambda: polls.append(1) or
                           len(polls) > chunk)
    assert out["preempted"] and out["steps"] == chunk
    caught_up()
    at = srv.pos

    with pytest.raises(SimulatedFailure):
        srv.decode_until(srv.pos + chunk + 2, fail_at=srv.pos + chunk)
    caught_up()
    srv.decode_until(8 + n)
    caught_up()
    assert _leaves_equal(srv.cache, want_cache)

    cold = DecodeServer(cfg, POLICY, mesh, run, max_seq=64, model=model)
    cold.restore(step=at)
    assert cold.pos == at
    np.testing.assert_array_equal(cold.decode(chunk + 1),
                                  want[:, :at + chunk + 2])


# four virtual devices only exist in a process of their own
_SHARDED = textwrap.dedent("""
    import sys, tempfile
    import jax
    from repro.configs import get_smoke_config
    from repro.data import TokenPipeline
    from repro.launch.mesh import make_host_mesh
    from repro.runtime.server import DecodeServer
    from repro.sharding import get_policy
    cfg = get_smoke_config(sys.argv[1])
    mesh = make_host_mesh(data=4, model=1)
    srv = DecodeServer(cfg, get_policy("baseline"), mesh, tempfile.mkdtemp(),
                       max_seq=64)
    on = (srv.model.param_shardings() if sys.argv[2] == "mesh"
          else jax.devices()[0])
    srv.load(jax.device_put(srv.model.init(jax.random.key(0)), on))
    srv.start(TokenPipeline(cfg, 4, 8, seed=9).next())
    for _ in range(2):
        srv.decode_until(srv.pos + 5)
    print("ENTRIES", srv._decode._cache_size(), srv.tokens.shape[1] - srv.pos)
""")


@pytest.mark.parametrize("placed", ["mesh", "device"])
def test_pipelined_decode_compiles_once_on_committed_weights(placed):
    """Weights committed across four devices, or to one, make every
    argmax output committed too: the token staged from the host for each
    call's first step is placed as those are, so the decode program keeps
    one entry."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, PYTHONPATH=os.path.join(repo, "src"),
               JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    r = subprocess.run([sys.executable, "-c", _SHARDED, "qwen1.5-0.5b", placed],
                       capture_output=True, text=True, env=env, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "ENTRIES 1 1" in r.stdout, r.stdout[-2000:]
