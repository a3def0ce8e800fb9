"""Plain reference of Mamba-2 (arXiv:2405.21060) as a language model:
its forward pass, in the interface of ``bench/judge.py``, and the
operations and bytes of its decode step.

Straightforward ``jax.numpy`` in float32, every matrix product at
``precision="highest"``, the state-space recurrence stepped one position
at a time.  It imports nothing of the program under test and takes
nothing the program made: weights come from ``bench/weights.py`` (drawn
again from the seed), prompts from ``bench/traffic.py``, and the served
tokens are what is being judged.  It reads the weights by the names of
the program's parameter tree.

Per layer, on ``u = RMSNorm(x)``: ``z = u Wz``, ``xs = u Wx``,
``B = u WB``, ``C = u WC``, ``dt = softplus(u Wdt + dt_bias)``; ``xs``,
``B`` and ``C`` each pass a causal depthwise convolution of width 4 and
SiLU; with ``A = -exp(A_log)`` per head, the state of each head steps
``h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t^T`` and reads
``y_t = h_t C_t + D x_t``; then ``x += RMSNorm(y * silu(z)) Wout``.  A
final RMSNorm and the tied embedding give the logits.  One departure
from the published block: it has a bias in its convolution, and the
program has none, so the reference takes it as zero.

``prec="fp8"`` is the control: the operands of every matrix
product rounded to float8 (e4m3, one scale per tensor).
"""
from __future__ import annotations

from typing import Any, Dict

from bench.reference.dense_lm import _einsum, _rmsnorm


def _conv(x, w):
    """Causal depthwise convolution: x (B, S, C), w (W, C)."""
    import jax.numpy as jnp
    W, S = w.shape[0], x.shape[1]
    xp = jnp.pad(x, ((0, 0), (W - 1, 0), (0, 0)))
    return sum(xp[:, k:k + S] * w[k] for k in range(W))


def _layer(x, p, c: Dict[str, Any], prec: str):
    import jax
    import jax.numpy as jnp
    eps = c["norm_epsilon"]
    P = c["headdim"]
    nh = c["expand"] * c["d_model"] // P
    m = p["mamba"]
    mm = lambda a, w: _einsum(prec, "bsd,de->bse", a, w)
    u = _rmsnorm(x, p["pre_mixer_norm"]["scale"], eps)
    z, xs, Bm, Cm = (mm(u, m[k]) for k in ("w_z", "w_x", "w_B", "w_C"))
    dt = jax.nn.softplus(mm(u, m["w_dt"]) + m["dt_bias"])      # (B, S, nh)
    xs = jax.nn.silu(_conv(xs, m["conv_x"]))
    Bm = jax.nn.silu(_conv(Bm, m["conv_B"]))
    Cm = jax.nn.silu(_conv(Cm, m["conv_C"]))
    A = -jnp.exp(m["A_log"])
    Bb, S = x.shape[:2]
    xh = xs.reshape(Bb, S, nh, P)

    def step(h, inp):
        x_t, dt_t, B_t, C_t = inp
        h = h * jnp.exp(dt_t * A)[..., None, None] \
            + (dt_t[..., None] * x_t)[..., None] * B_t[:, None, None, :]
        return h, jnp.einsum("bhpn,bn->bhp", h, C_t, precision="highest")

    h0 = jnp.zeros((Bb, nh, P, c["d_state"]), jnp.float32)
    tm = lambda a: jnp.moveaxis(a, 1, 0)
    _, y = jax.lax.scan(step, h0, (tm(xh), tm(dt), tm(Bm), tm(Cm)))
    y = jnp.moveaxis(y, 0, 1) + m["D"][:, None] * xh
    y = _rmsnorm(y.reshape(Bb, S, -1) * jax.nn.silu(z), m["norm"], eps)
    return x + mm(y, m["w_out"])


def hidden(params, tokens, c: Dict[str, Any], prec: str = "f32"):
    """Final normed hidden states (B, S, d) of prompts and tokens."""
    import jax
    x = params["embed"]["tok"][tokens]
    x, _ = jax.lax.scan(lambda x, lp: (_layer(x, lp, c, prec), None), x,
                        params["blocks"]["pos0"])
    return _rmsnorm(x, params["final_norm"]["scale"], c["norm_epsilon"])


def logits(params, h, c: Dict[str, Any], prec: str = "f32"):
    """Logits of hidden states (..., d) against the tied embedding; the
    padding rows at -1e30."""
    import jax.numpy as jnp
    lg = _einsum(prec, "...d,vd->...v", h, params["embed"]["tok"])
    return jnp.where(jnp.arange(lg.shape[-1]) < c["vocab_size"], lg, -1e30)


# --------------------------------------------------------------- counts
def _layer_matmul(c: Dict[str, Any]) -> int:
    d, di = c["d_model"], c["expand"] * c["d_model"]
    nh = di // c["headdim"]
    return d * (2 * di + 2 * c["d_state"] * c["ngroups"] + nh) + di * d


def param_count(c: Dict[str, Any], vocab_multiple: int = 256) -> int:
    """Every parameter of the program's layout of this model: no
    convolution bias, a second (unused) pre-norm gain per layer, the
    vocabulary padded to ``vocab_multiple``."""
    d, di = c["d_model"], c["expand"] * c["d_model"]
    nh = di // c["headdim"]
    n = c["d_state"] * c["ngroups"]
    conv = c["d_conv"] * (di + 2 * n)
    layer = _layer_matmul(c) + conv + 3 * nh + di + 2 * d
    emb = -(-c["vocab_size"] // vocab_multiple) * vocab_multiple * d
    return c["n_layer"] * layer + emb + d


def decode_flops(c: Dict[str, Any], batch: int) -> float:
    """One decode step of a batch: the products against every weight and
    the tied head, and the state update and read (about 4 operations
    per state element per head)."""
    di = c["expand"] * c["d_model"]
    state = di * c["d_state"]
    per_row = 2 * (c["n_layer"] * _layer_matmul(c)
                   + c["d_model"] * c["vocab_size"]) \
        + c["n_layer"] * 4 * state
    return float(batch * per_row)


def decode_bytes(c: Dict[str, Any], batch: int, state_bytes: int = 4,
                 conv_bytes: int = 2) -> float:
    """One decode step's least traffic: every weight once in bfloat16
    (the tied head over the true vocabulary), and the recurrent state
    (float32) and convolution tails (bfloat16) read and written once."""
    di = c["expand"] * c["d_model"]
    n = c["d_state"] * c["ngroups"]
    weights = 2 * (c["n_layer"] * (_layer_matmul(c)
                                   + c["d_conv"] * (di + 2 * n))
                   + c["d_model"] * c["vocab_size"])
    state = c["n_layer"] * batch * (
        di * c["d_state"] * state_bytes
        + (c["d_conv"] - 1) * (di + 2 * n) * conv_bytes)
    return float(weights + 2 * state)
