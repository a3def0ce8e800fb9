"""Plain reference of IBM Granite 4.0-H (``granitemoehybrid``), a hybrid
of Mamba-2 and attention layers each followed by a mixture of experts:
its forward pass, in the interface of ``bench/judge.py``, and the
operations and bytes of its decode step.

Straightforward ``jax.numpy`` in float32, every matrix product at
``precision="highest"``, the state-space recurrence stepped one position
at a time (as in ``bench/reference/mamba2.py``, whose convolution it
uses), attention over the whole causal prefix with no cache, and the
held experts computed densely on every token, each weighted by its gate
where the router chose it.  It imports nothing of the program under
test: weights come from ``bench/weights.py`` (drawn again from the seed,
in the dtype the program holds them in, and read here in float32) and
it reads them by the names of the program's parameter tree.  It runs
layer by layer, a row at a time inside each layer, so that the whole
batch at the served length fits one chip beside the weights.

The model, from the published ``config.json`` (the keys below are its
own): ``x = embedding_multiplier * E[tok]``; then per layer
``x += residual_multiplier * Mixer(RMSNorm(x))``, where the mixer is the
Mamba-2 block of ``mamba2.py`` (``mamba_n_heads`` heads of
``mamba_d_head``, state ``mamba_d_state``, one group) or, where
``layer_types`` says ``attention``, causal GQA attention with no
positional encoding and scores scaled by ``attention_multiplier``; then
``x += residual_multiplier * (sum over e in top-k and held of
g_e SwiGLU_e(v) + SwiGLU_shared(v))`` with ``v = RMSNorm(x)`` and ``g``
the softmax over the ``num_experts_per_tok`` largest of the
``router_experts`` router logits; a final RMSNorm, and logits against
the tied embedding divided by ``logits_scaling``.

The chip's share (``model-configs`` section 4): the router scores all
``router_experts`` experts, but only the ``num_local_experts`` held
here (the first ones) add their part; what the others would add is left
out, as it is in the program.  The vocabulary is the slice held here.

Departures from the published model: the Mamba-2 convolution has a bias
there and none in the program, so it is taken as zero here; the program
keeps a pre-norm gain per layer for each sub-layer as Granite does.

``prec="fp8"`` is the control: the operands of every matrix product
rounded to float8 (e4m3, one scale per tensor).
"""
from __future__ import annotations

from typing import Any, Dict, List

from bench.reference.dense_lm import _einsum, _rmsnorm
from bench.reference.mamba2 import _conv

Q_BLOCK = 512          # attention queries per block: a row's scores fit


def _f32(w):
    import jax.numpy as jnp
    return w.astype(jnp.float32)


def _kinds(c: Dict[str, Any]) -> List[str]:
    """The kinds of the layers held here: the first ``num_hidden_layers``
    of the published ``layer_types``."""
    return list(c["layer_types"][:c["num_hidden_layers"]])


def _ssm_heads(c: Dict[str, Any]) -> int:
    nh = c["mamba_expand"] * c["hidden_size"] // c["mamba_d_head"]
    assert c["mamba_n_groups"] == 1, "one group of B and C"
    return nh


# ----------------------------------------------------------- sub-layers
def _mamba(u, m, c: Dict[str, Any], prec: str):
    """The Mamba-2 mixer on one row ``u`` (S, d), normed."""
    import jax
    import jax.numpy as jnp
    S = u.shape[0]
    P, nh = c["mamba_d_head"], _ssm_heads(c)
    mm = lambda a, w: _einsum(prec, "sd,de->se", a, _f32(w))
    z, xs, Bm, Cm = (mm(u, m[k]) for k in ("w_z", "w_x", "w_B", "w_C"))
    dt = jax.nn.softplus(mm(u, m["w_dt"]) + _f32(m["dt_bias"]))  # (S, nh)
    conv = lambda a, w: jax.nn.silu(_conv(a[None], _f32(w))[0])
    xs, Bm, Cm = (conv(xs, m["conv_x"]), conv(Bm, m["conv_B"]),
                  conv(Cm, m["conv_C"]))
    A = -jnp.exp(_f32(m["A_log"]))
    xh = xs.reshape(S, nh, P)

    def step(h, inp):
        x_t, dt_t, B_t, C_t = inp
        h = h * jnp.exp(dt_t * A)[:, None, None] \
            + (dt_t[:, None] * x_t)[..., None] * B_t[None, None, :]
        return h, jnp.einsum("hpn,n->hp", h, C_t, precision="highest")

    h0 = jnp.zeros((nh, P, c["mamba_d_state"]), jnp.float32)
    _, y = jax.lax.scan(step, h0, (xh, dt, Bm, Cm))
    y = y + _f32(m["D"])[:, None] * xh
    y = _rmsnorm(y.reshape(S, -1) * jax.nn.silu(z), _f32(m["norm"]),
                 c["rms_norm_eps"])
    return mm(y, m["w_out"])


def _attention(u, a, c: Dict[str, Any], prec: str):
    """Causal GQA attention with no positional encoding on one row ``u``
    (S, d), normed; the queries in blocks of ``Q_BLOCK``."""
    import jax
    import jax.numpy as jnp
    S = u.shape[0]
    H, KV = c["num_attention_heads"], c["num_key_value_heads"]
    hd = c["head_dim"]
    mm = lambda a_, w: _einsum(prec, "sd,de->se", a_, _f32(w))
    q = mm(u, a["wq"]).reshape(S, H, hd)
    k = jnp.repeat(mm(u, a["wk"]).reshape(S, KV, hd), H // KV, axis=1)
    v = jnp.repeat(mm(u, a["wv"]).reshape(S, KV, hd), H // KV, axis=1)
    nb = -(-S // Q_BLOCK)
    qb = jnp.pad(q, ((0, nb * Q_BLOCK - S), (0, 0), (0, 0)))
    qb = qb.reshape(nb, Q_BLOCK, H, hd)

    def block(args):
        i, q_i = args
        s = _einsum(prec, "qhd,khd->hqk", q_i, k) \
            * c["attention_multiplier"]
        rows = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        s = jnp.where(jnp.arange(S)[None, None, :] <= rows[None, :, None],
                      s, -1e30)
        return _einsum(prec, "hqk,khd->qhd", jax.nn.softmax(s, -1), v)

    o = jax.lax.map(block, (jnp.arange(nb), qb)).reshape(nb * Q_BLOCK, -1)
    return mm(o[:S], a["wo"])


def _swiglu(v, w_gate, w_up, w_down, prec: str):
    import jax
    mm = lambda a, w: _einsum(prec, "sd,df->sf", a, _f32(w))
    return mm(jax.nn.silu(mm(v, w_gate)) * mm(v, w_up), w_down)


def _moe(v, p, c: Dict[str, Any], prec: str):
    """The held experts' part of the routed mixture, and the shared
    expert, on one row ``v`` (S, d), normed."""
    import jax
    import jax.numpy as jnp
    logits = _einsum(prec, "sd,de->se", v, _f32(p["router"]))   # (S, E)
    top, idx = jax.lax.top_k(logits, c["num_experts_per_tok"])
    g = jax.nn.softmax(top, -1)                                   # (S, k)
    y = _swiglu(v, p["shared"]["w_gate"], p["shared"]["w_up"],
                p["shared"]["w_down"], prec)
    for e in range(c["num_local_experts"]):
        gate = jnp.sum(jnp.where(idx == e, g, 0.0), -1)           # (S,)
        y = y + gate[:, None] * _swiglu(v, p["w_gate"][e], p["w_up"][e],
                                        p["w_down"][e], prec)
    return y


def layer(x, lp, kind: str, c: Dict[str, Any], prec: str = "f32"):
    """One layer on one row ``x`` (S, d): the mixer, then the experts,
    each residual branch scaled."""
    eps, r = c["rms_norm_eps"], c["residual_multiplier"]
    u = _rmsnorm(x, _f32(lp["pre_mixer_norm"]["scale"]), eps)
    if kind == "attention":
        x = x + r * _attention(u, lp["attn"], c, prec)
    else:
        x = x + r * _mamba(u, lp["mamba"], c, prec)
    v = _rmsnorm(x, _f32(lp["pre_mlp_norm"]["scale"]), eps)
    return x + r * _moe(v, lp["moe"], c, prec)


# ---------------------------------------------------------------- model
def hidden(params, tokens, c: Dict[str, Any], prec: str = "f32"):
    """Final normed hidden states (B, S, d) of rows (B, S): layer by
    layer, a row at a time within each layer."""
    import jax
    blocks = params["blocks"]
    period = len(blocks)               # the program stacks by pattern place
    x = c["embedding_multiplier"] * _f32(params["embed"]["tok"])[tokens]
    for i, kind in enumerate(_kinds(c)):
        lp = jax.tree.map(lambda a: a[i // period],
                          blocks[f"pos{i % period}"])
        assert ("attn" in lp) == (kind == "attention"), (i, kind)
        x = jax.lax.map(lambda row: layer(row, lp, kind, c, prec), x)
    return _rmsnorm(x, _f32(params["final_norm"]["scale"]),
                    c["rms_norm_eps"])


def logits(params, h, c: Dict[str, Any], prec: str = "f32"):
    """Logits of hidden states (..., d) against the tied embedding over
    the padded vocabulary, divided by ``logits_scaling``; the padding
    rows at -1e30."""
    import jax.numpy as jnp
    lg = _einsum(prec, "...d,vd->...v", h, _f32(params["embed"]["tok"]))
    lg = lg / c["logits_scaling"]
    return jnp.where(jnp.arange(lg.shape[-1]) < c["vocab_size"], lg, -1e30)


# --------------------------------------------------------------- counts
def _per_layer(c: Dict[str, Any]) -> Dict[str, int]:
    """Parameters of each sub-layer in the program's layout."""
    d = c["hidden_size"]
    di, N = c["mamba_expand"] * d, c["mamba_d_state"]
    nh, W = _ssm_heads(c), c["mamba_d_conv"]
    H, KV, hd = (c["num_attention_heads"], c["num_key_value_heads"],
                 c["head_dim"])
    f, fs = c["intermediate_size"], c["shared_intermediate_size"]
    return {
        "mamba_matmul": d * (2 * di + 2 * N + nh) + di * d,
        "mamba_other": W * (di + 2 * N) + 3 * nh + di,   # conv, A, D, dt, norm
        "attn": d * H * hd + 2 * d * KV * hd + H * hd * d,
        "router": d * c["router_experts"],
        "expert": 3 * d * f,
        "shared": 3 * d * fs,
        "norms": 2 * d,
    }


def _counts(c: Dict[str, Any]):
    kinds = _kinds(c)
    return kinds.count("mamba"), kinds.count("attention")


def _weights(c: Dict[str, Any], vocab: int) -> int:
    n = _per_layer(c)
    n_mamba, n_attn = _counts(c)
    layers = (n_mamba * (n["mamba_matmul"] + n["mamba_other"])
              + n_attn * n["attn"]
              + (n_mamba + n_attn) * (n["router"] + n["shared"] + n["norms"]
                                      + c["num_local_experts"] * n["expert"]))
    return layers + vocab * c["hidden_size"] + c["hidden_size"]


def param_count(c: Dict[str, Any], vocab_multiple: int = 256) -> int:
    """Every parameter of the program's layout of this cut: the held
    experts, the shared expert, the whole router, no convolution bias,
    the vocabulary slice padded to ``vocab_multiple``."""
    return _weights(c, -(-c["vocab_size"] // vocab_multiple) * vocab_multiple)


def decode_flops(c: Dict[str, Any], batch: int, pos: float) -> float:
    """One decode step of a batch at position ``pos``: the products
    against every weight a token needs (the routed experts at their
    expected share, ``num_experts_per_tok`` x held / ``router_experts``
    of a token, plus the shared expert and the router), the tied head,
    the state update and read (about 4 operations per state element),
    and the scores and values over the ``pos + 1`` keys of each
    attention layer."""
    n = _per_layer(c)
    n_mamba, n_attn = _counts(c)
    routed = (c["num_experts_per_tok"] * c["num_local_experts"]
              / c["router_experts"])
    matmul = (n_mamba * n["mamba_matmul"] + n_attn * n["attn"]
              + (n_mamba + n_attn) * (n["router"] + n["shared"]
                                      + routed * n["expert"])
              + c["hidden_size"] * c["vocab_size"])
    state = 4 * c["mamba_expand"] * c["hidden_size"] * c["mamba_d_state"]
    attn = 4 * c["num_attention_heads"] * c["head_dim"] * (pos + 1)
    return float(batch * (2 * matmul + n_mamba * state + n_attn * attn))


def decode_bytes(c: Dict[str, Any], batch: int, pos: float,
                 state_bytes: int = 4, cache_bytes: int = 2) -> float:
    """One decode step's least traffic at position ``pos``: every held
    weight once in bfloat16 (the tied head over the vocabulary slice);
    the recurrent state (float32) and convolution tails (bfloat16) read
    and written once; the key/value rows valid at ``pos`` (``pos + 1``)
    read once and one new row written (bfloat16).  The cache's padding
    past ``pos`` is not counted: the algorithm does not need it."""
    d = c["hidden_size"]
    di, N = c["mamba_expand"] * d, c["mamba_d_state"]
    n_mamba, n_attn = _counts(c)
    state = n_mamba * batch * (
        _ssm_heads(c) * c["mamba_d_head"] * N * state_bytes
        + (c["mamba_d_conv"] - 1) * (di + 2 * N) * cache_bytes)
    row = 2 * c["num_key_value_heads"] * c["head_dim"] * cache_bytes
    kv = n_attn * batch * row * (pos + 2)
    return float(2 * _weights(c, c["vocab_size"]) + 2 * state + kv)
