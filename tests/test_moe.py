"""MoE dispatch correctness: capacity math, drop semantics, EP shard_map
path vs single-device fallback parity."""
import jax
import jax.numpy as jnp
import numpy as np
from repro.configs import get_smoke_config
from repro.launch.mesh import make_mesh
from repro.models import moe as MOE
from repro.sharding import get_policy

POLICY = get_policy("baseline")


def _setup(E=4, k=2, d=16, f=32, T=24, seed=0, capacity_factor=8.0):
    import dataclasses
    cfg = get_smoke_config("qwen3-moe-30b-a3b",
                           moe_num_experts=E, moe_top_k=k,
                           d_model=d, moe_d_ff=f,
                           moe_capacity_factor=capacity_factor)
    ks = jax.random.split(jax.random.key(seed), 5)
    params = {
        "router": jax.random.normal(ks[0], (d, E), jnp.float32) * 0.1,
        "w_gate": jax.random.normal(ks[1], (E, d, f), jnp.float32) * 0.1,
        "w_up": jax.random.normal(ks[2], (E, d, f), jnp.float32) * 0.1,
        "w_down": jax.random.normal(ks[3], (E, f, d), jnp.float32) * 0.1,
    }
    x = jax.random.normal(ks[4], (2, T // 2, d), jnp.float32)
    return cfg, params, x


def _dense_moe_reference(params, cfg, x):
    """Dense (all-experts) reference: route, compute every expert for every
    token, mix top-k — no capacity, no dispatch tables."""
    B, S, d = x.shape
    xt = x.reshape(-1, d)
    logits = xt @ params["router"]
    probs = jax.nn.softmax(logits, axis=-1)
    top_w, top_e = jax.lax.top_k(probs, cfg.moe_top_k)
    top_w = top_w / top_w.sum(-1, keepdims=True)
    g = jnp.einsum("td,edf->tef", xt, params["w_gate"])
    u = jnp.einsum("td,edf->tef", xt, params["w_up"])
    h = jax.nn.silu(g) * u
    out_all = jnp.einsum("tef,efd->ted", h, params["w_down"])
    mix = jnp.zeros_like(xt)
    for j in range(cfg.moe_top_k):
        sel = jnp.take_along_axis(out_all, top_e[:, j][:, None, None],
                                  axis=1)[:, 0]
        mix = mix + top_w[:, j:j + 1] * sel
    return mix.reshape(B, S, d)


def test_fallback_matches_dense_reference():
    cfg, params, x = _setup()
    y, aux = MOE.moe_block(params, cfg, x, POLICY, None, dropless=True)
    ref = _dense_moe_reference(params, cfg, x)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    assert np.isfinite(float(aux)) and float(aux) > 0


def test_shard_map_path_matches_fallback():
    cfg, params, x = _setup()
    mesh = make_mesh((1, 1), ("data", "model"))
    y0, aux0 = MOE.moe_block(params, cfg, x, POLICY, None, dropless=True)
    with jax.sharding.set_mesh(mesh):
        y1, aux1 = MOE.moe_block(params, cfg, x, POLICY.for_mesh(mesh),
                                 mesh, dropless=True)
    np.testing.assert_allclose(np.asarray(y0), np.asarray(y1),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(float(aux0), float(aux1), rtol=1e-5)


def test_capacity_formula():
    assert MOE.capacity(tokens=64, k=2, num_experts=8, factor=1.0) == 16
    assert MOE.capacity(tokens=64, k=2, num_experts=8, factor=1.25) == 20
    # capped at tokens
    assert MOE.capacity(tokens=4, k=2, num_experts=1, factor=10.0) == 4
    # at least k
    assert MOE.capacity(tokens=2, k=2, num_experts=64, factor=1.0) >= 2


def test_tight_capacity_drops_tokens():
    """With factor << 1 some tokens overflow expert capacity and their
    contribution is dropped (GShard semantics) — output differs from the
    dropless run but stays finite."""
    cfg, params, x = _setup(capacity_factor=0.25)
    y_drop, _ = MOE.moe_block(params, cfg, x, POLICY, None, dropless=False)
    y_full, _ = MOE.moe_block(params, cfg, x, POLICY, None, dropless=True)
    assert bool(jnp.all(jnp.isfinite(y_drop)))
    assert float(jnp.max(jnp.abs(y_drop - y_full))) > 1e-6


def test_aux_loss_uniform_routing_is_one():
    """Perfectly uniform routing gives the Switch aux loss its minimum
    E * (1/E) * (1/E) * E = 1."""
    cfg, params, x = _setup(E=4)
    params = dict(params, router=jnp.zeros_like(params["router"]))
    _, aux = MOE.moe_block(params, cfg, x, POLICY, None, dropless=True)
    # ties in top_k break deterministically; P_e is exactly uniform
    assert 0.9 < float(aux) < 1.6


def test_moe_grads_flow_to_all_parts():
    cfg, params, x = _setup()

    def loss(p):
        y, aux = MOE.moe_block(p, cfg, x, POLICY, None, dropless=True)
        return jnp.sum(y ** 2) + 0.01 * aux

    g = jax.grad(loss)(params)
    for name in ("router", "w_gate", "w_up", "w_down"):
        assert float(jnp.max(jnp.abs(g[name]))) > 0, name
