"""Plain reference of a pre-norm decoder language model of the Qwen2
family (Qwen1.5): its forward pass, in the interface of
``bench/judge.py``, which trains and judges with it.

Straightforward ``jax.numpy`` in float32, every matrix product at
``precision="highest"``, one row at a time, no kernels, no cache, no
sharding.  It imports nothing of the program under test and takes
nothing the program made: weights come from ``bench/weights.py`` (drawn
again from the seed), batches from ``bench/traffic.py``.  It reads the
weights by the names of the program's parameter tree, which is the only
thing the two share.

The model, from the published architecture (``config.json`` of
Qwen/Qwen1.5-0.5B): token embedding; per layer
``x += Wo·attn(rope(Wq·n(x)+bq), rope(Wk·n(x)+bk), Wv·n(x)+bv)`` with a
causal softmax over ``q·k/sqrt(head_dim)`` and rotary embeddings that
rotate the two halves of each head; then
``x += Wdown·(silu(Wgate·n(x)) * Wup·n(x))``; a final RMSNorm; logits
against the tied embedding.  ``n`` is RMSNorm with a learned gain.

``precision="fp8"`` is the control: every matrix product's operands
are rounded to float8 (e4m3, one scale per tensor) in the forward pass.
"""
from __future__ import annotations

import functools
from typing import Any, Dict

import numpy as np


def _fp8(x):
    import jax
    import jax.numpy as jnp
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    q = (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
    return x + jax.lax.stop_gradient(q - x)     # gradient taken as f32


def _einsum(precision: str, spec: str, a, b):
    import jax.numpy as jnp
    if precision == "fp8":
        a, b = _fp8(a), _fp8(b)
    return jnp.einsum(spec, a, b, precision="highest")


def _rmsnorm(x, scale, eps):
    import jax.numpy as jnp
    return x * (1.0 / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps)) \
        * scale


def _rope(x, theta):
    """x (T, heads, hd): rotate the two halves of each head."""
    import jax.numpy as jnp
    T, _, hd = x.shape
    half = hd // 2
    inv = 1.0 / theta ** (np.arange(half, dtype=np.float64) / half)
    ang = np.arange(T)[:, None] * inv[None, :]
    cos = jnp.asarray(np.cos(ang), jnp.float32)[:, None, :]
    sin = jnp.asarray(np.sin(ang), jnp.float32)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(x, p, c: Dict[str, Any], prec: str):
    import jax
    import jax.numpy as jnp
    T = x.shape[0]
    H, KV = c["num_attention_heads"], c["num_key_value_heads"]
    hd = c["head_dim"]
    eps = c["rms_norm_eps"]
    mm = functools.partial(_einsum, prec)
    a = p["attn"]
    h = _rmsnorm(x, p["pre_mixer_norm"]["scale"], eps)
    q = (mm("td,de->te", h, a["wq"]) + a["bq"]).reshape(T, H, hd)
    k = (mm("td,de->te", h, a["wk"]) + a["bk"]).reshape(T, KV, hd)
    v = (mm("td,de->te", h, a["wv"]) + a["bv"]).reshape(T, KV, hd)
    q, k = _rope(q, c["rope_theta"]), _rope(k, c["rope_theta"])
    k, v = jnp.repeat(k, H // KV, axis=1), jnp.repeat(v, H // KV, axis=1)
    s = mm("qhd,khd->hqk", q, k) / np.sqrt(hd)
    causal = np.tril(np.ones((T, T), bool))
    s = jnp.where(causal[None], s, -1e30)
    o = mm("hqk,khd->qhd", jax.nn.softmax(s, -1), v).reshape(T, H * hd)
    x = x + mm("te,ed->td", o, a["wo"])
    m = p["mlp"]
    h = _rmsnorm(x, p["pre_mlp_norm"]["scale"], eps)
    g = jax.nn.silu(mm("td,df->tf", h, m["w_gate"]))
    return x + mm("tf,fd->td", g * mm("td,df->tf", h, m["w_up"]),
                  m["w_down"])


def _row(params, tokens, c: Dict[str, Any], prec: str):
    """Final normed hidden states (T, d) of one row (T,)."""
    import jax
    x = params["embed"]["tok"][tokens]
    x, _ = jax.lax.scan(lambda x, lp: (_layer(x, lp, c, prec), None), x,
                        params["blocks"]["pos0"])
    return _rmsnorm(x, params["final_norm"]["scale"], c["rms_norm_eps"])


def hidden(params, tokens, c: Dict[str, Any], prec: str = "f32"):
    """Final normed hidden states (B, S, d) of rows (B, S), a row at a
    time."""
    import jax
    return jax.lax.map(lambda row: _row(params, row, c, prec), tokens)


def logits(params, h, c: Dict[str, Any], prec: str = "f32"):
    """Logits of hidden states (..., d) over the padded vocabulary; the
    padding rows at -1e30."""
    import jax.numpy as jnp
    emb = params["embed"]["tok"]
    head = emb if c["tie_word_embeddings"] else params["lm_head"].T
    lg = _einsum(prec, "...d,vd->...v", h, head)
    return jnp.where(jnp.arange(lg.shape[-1]) < c["vocab_size"], lg, -1e30)


def param_count(c: Dict[str, Any], vocab_multiple: int = 256) -> int:
    """Every parameter of the program's layout of this model, the
    embedding's padding rows included (the program pads the vocabulary
    to ``vocab_multiple``)."""
    d, ff, L = c["hidden_size"], c["intermediate_size"], c["num_hidden_layers"]
    qd = c["num_attention_heads"] * c["head_dim"]
    kvd = c["num_key_value_heads"] * c["head_dim"]
    attn = d * qd + 2 * d * kvd + qd * d + qd + 2 * kvd   # with q/k/v bias
    layer = attn + 3 * d * ff + 2 * d                      # + two norm gains
    emb = -(-c["vocab_size"] // vocab_multiple) * vocab_multiple * d
    head = 0 if c["tie_word_embeddings"] else emb
    return L * layer + emb + head + d
