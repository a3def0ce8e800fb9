"""Mamba2 (SSD — state-space duality) block, pure-JAX reference path.

The chunked SSD algorithm here is the oracle for ``repro.kernels.ssd_scan``
(the Pallas TPU kernel) and the implementation used by the dry-run lowering.

Shapes:  x (B, S, d_model) -> y (B, S, d_model)
Internal: d_inner = expand*d_model, nh = d_inner/headdim heads, state N.
Training/prefill uses the chunked scan (O(S·Q) + O(S·N·P)); decode is the
O(1)-per-token recurrence on a (B, nh, P, N) state.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.models.layers import ParamSpec, rmsnorm
from repro.sharding.policy import ShardingPolicy, constrain


def mamba_specs(cfg) -> Dict[str, ParamSpec]:
    d, di, N, nh = cfg.d_model, cfg.d_inner, cfg.ssm_state, cfg.ssm_nheads
    w = cfg.ssm_conv_width
    return {
        "w_x": ParamSpec((d, di), ("d_model", "ssm_inner"), cast=True),
        "w_z": ParamSpec((d, di), ("d_model", "ssm_inner"), cast=True),
        "w_B": ParamSpec((d, N), ("d_model", "state"), cast=True),
        "w_C": ParamSpec((d, N), ("d_model", "state"), cast=True),
        "w_dt": ParamSpec((d, nh), ("d_model", "ssm_heads"), cast=True),
        "dt_bias": ParamSpec((nh,), ("ssm_heads",), init="zeros"),
        "A_log": ParamSpec((nh,), ("ssm_heads",), init="zeros"),
        "D": ParamSpec((nh,), ("ssm_heads",), init="ones"),
        "conv_x": ParamSpec((w, di), ("conv", "ssm_inner")),
        "conv_B": ParamSpec((w, N), ("conv", "state")),
        "conv_C": ParamSpec((w, N), ("conv", "state")),
        "norm": ParamSpec((di,), ("ssm_inner",), init="ones"),
        "w_out": ParamSpec((di, d), ("ssm_inner", "d_model"), cast=True),
    }


# ----------------------------------------------------------------------
# causal depthwise conv
# ----------------------------------------------------------------------
def causal_conv(x: jax.Array, w: jax.Array) -> jax.Array:
    """x (B, S, C), w (W, C) depthwise causal convolution."""
    W = w.shape[0]
    xp = jnp.pad(x, ((0, 0), (W - 1, 0), (0, 0)))
    out = jax.lax.conv_general_dilated(
        xp, w[:, None, :].astype(x.dtype),           # (W, 1, C)
        window_strides=(1,), padding="VALID",
        dimension_numbers=("NWC", "WIO", "NWC"),
        feature_group_count=x.shape[-1])
    return out


def conv_step(x_new: jax.Array, conv_state: jax.Array, w: jax.Array
              ) -> Tuple[jax.Array, jax.Array]:
    """One decode step.  x_new (B, C), conv_state (B, W-1, C), w (W, C)."""
    full = jnp.concatenate([conv_state, x_new[:, None, :]], axis=1)   # (B,W,C)
    y = jnp.einsum("bwc,wc->bc", full.astype(jnp.float32),
                   w.astype(jnp.float32)).astype(x_new.dtype)
    return y, full[:, 1:, :]


# ----------------------------------------------------------------------
# chunked SSD scan (training / prefill)
# ----------------------------------------------------------------------
def ssd_chunked(x: jax.Array, dt: jax.Array, A: jax.Array,
                Bm: jax.Array, Cm: jax.Array, chunk: int,
                h0: jax.Array | None = None
                ) -> Tuple[jax.Array, jax.Array]:
    """SSD over one sequence.

    x  (B, S, nh, P)   inputs per head
    dt (B, S, nh)      positive step sizes (post-softplus)
    A  (nh,)           negative decay rates
    Bm (B, S, N), Cm (B, S, N)   input/output projections (single group)
    Returns y (B, S, nh, P) and final state (B, nh, P, N).
    """
    B, S, nh, P = x.shape
    N = Bm.shape[-1]
    Q = min(chunk, S)
    if S % Q:
        # pad with dt=0 steps: decay exp(0)=1, contribution 0 — a no-op
        # for the recurrence, sliced off the output below.
        pad = Q - S % Q
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        Bm = jnp.pad(Bm, ((0, 0), (0, pad), (0, 0)))
        Cm = jnp.pad(Cm, ((0, 0), (0, pad), (0, 0)))
    S_pad = x.shape[1]
    nC = S_pad // Q

    xf = x.astype(jnp.float32).reshape(B, nC, Q, nh, P)
    dtf = dt.astype(jnp.float32).reshape(B, nC, Q, nh)
    Bf = Bm.astype(jnp.float32).reshape(B, nC, Q, N)
    Cf = Cm.astype(jnp.float32).reshape(B, nC, Q, N)
    Af = A.astype(jnp.float32)

    dA = dtf * Af                                    # (B,nC,Q,nh)
    cum = jnp.cumsum(dA, axis=2)                     # inclusive
    # decay from chunk entry to position i (state contribution)
    decay_in = jnp.exp(cum)                          # (B,nC,Q,nh)
    # decay from position j to chunk exit
    total = cum[:, :, -1:, :]                        # (B,nC,1,nh)
    decay_out = jnp.exp(total - cum)                 # (B,nC,Q,nh)
    chunk_decay = jnp.exp(total[:, :, 0, :])         # (B,nC,nh)

    # intra-chunk (quadratic within chunk):
    # L[i,j] = exp(cum_i - cum_j) for j<=i
    diff = cum[:, :, :, None, :] - cum[:, :, None, :, :]   # (B,nC,Qi,Qj,nh)
    mask = (jnp.arange(Q)[:, None] >= jnp.arange(Q)[None, :])
    L = jnp.where(mask[None, None, :, :, None], jnp.exp(diff), 0.0)
    CB = jnp.einsum("bcin,bcjn->bcij", Cf, Bf)             # (B,nC,Q,Q)
    G = CB[..., None] * L                                  # (B,nC,Qi,Qj,nh)
    y_intra = jnp.einsum("bcijh,bcjh,bcjhp->bcihp", G, dtf, xf)

    # inter-chunk recurrence
    # state contribution of chunk c: sum_j decay_out[j] * dt[j] * B[j] ⊗ x[j]
    state_contrib = jnp.einsum("bcjh,bcjh,bcjn,bcjhp->bchpn",
                               decay_out, dtf, Bf, xf)      # (B,nC,nh,P,N)

    if h0 is None:
        h0 = jnp.zeros((B, nh, P, N), jnp.float32)

    def step(h, inputs):
        contrib, cdecay = inputs                            # (B,nh,P,N),(B,nh)
        h_new = h * cdecay[:, :, None, None] + contrib
        return h_new, h

    (h_final, h_prevs) = jax.lax.scan(
        step,
        h0.astype(jnp.float32),
        (jnp.moveaxis(state_contrib, 1, 0), jnp.moveaxis(chunk_decay, 1, 0)))
    h_prevs = jnp.moveaxis(h_prevs, 0, 1)                   # (B,nC,nh,P,N)

    # y_inter[i] = decay_in[i] * C[i] · h_prev
    y_inter = jnp.einsum("bcin,bchpn,bcih->bcihp", Cf, h_prevs, decay_in)

    y = (y_intra + y_inter).reshape(B, S_pad, nh, P)[:, :S]
    return y.astype(x.dtype), h_final


def ssd_decode_step(x: jax.Array, dt: jax.Array, A: jax.Array,
                    Bm: jax.Array, Cm: jax.Array, h: jax.Array
                    ) -> Tuple[jax.Array, jax.Array]:
    """One-token recurrence.  x (B,nh,P), dt (B,nh), Bm/Cm (B,N),
    h (B,nh,P,N) -> y (B,nh,P), h_new."""
    xf = x.astype(jnp.float32)
    dtf = dt.astype(jnp.float32)
    dA = jnp.exp(dtf * A.astype(jnp.float32))                  # (B,nh)
    contrib = jnp.einsum("bh,bn,bhp->bhpn", dtf, Bm.astype(jnp.float32), xf)
    h_new = h * dA[:, :, None, None] + contrib
    y = jnp.einsum("bn,bhpn->bhp", Cm.astype(jnp.float32), h_new)
    return y.astype(x.dtype), h_new


# ----------------------------------------------------------------------
# full block
# ----------------------------------------------------------------------
def mamba_block(params, cfg, x: jax.Array, policy: ShardingPolicy,
                use_kernels: bool = False) -> jax.Array:
    """Training/prefill forward.  x (B, S, d_model)."""
    B, S, _ = x.shape
    di, N, nh, P = cfg.d_inner, cfg.ssm_state, cfg.ssm_nheads, cfg.ssm_headdim
    dt_ = x.dtype
    xz = x @ params["w_z"].astype(dt_)                     # gate
    xi = x @ params["w_x"].astype(dt_)
    Bm = x @ params["w_B"].astype(dt_)
    Cm = x @ params["w_C"].astype(dt_)
    dt = x @ params["w_dt"].astype(dt_)
    xi = constrain(xi, policy, "batch", "seq", "ssm_inner")

    xi = jax.nn.silu(causal_conv(xi, params["conv_x"]))
    Bm = jax.nn.silu(causal_conv(Bm, params["conv_B"]))
    Cm = jax.nn.silu(causal_conv(Cm, params["conv_C"]))
    dt = jax.nn.softplus(dt.astype(jnp.float32) +
                         params["dt_bias"].astype(jnp.float32))
    A = -jnp.exp(params["A_log"].astype(jnp.float32))

    xh = xi.reshape(B, S, nh, P)
    xh = constrain(xh, policy, "batch", "seq", "ssm_heads", None)
    if use_kernels:
        from repro.kernels import ops
        y, _ = ops.ssd(xh, dt, A, Bm, Cm, chunk=cfg.ssm_chunk)
    else:
        y, _ = ssd_chunked(xh, dt, A, Bm, Cm, chunk=cfg.ssm_chunk)
    y = (y + params["D"].astype(jnp.float32)[None, None, :, None] * xh
         ).astype(dt_)
    y = y.reshape(B, S, di)
    y = rmsnorm({"scale": params["norm"]}, y * jax.nn.silu(xz), cfg.norm_eps)
    return y @ params["w_out"].astype(dt_)


def mamba_cache_init(cfg, batch: int, dtype=jnp.float32):
    di, N, nh, P = cfg.d_inner, cfg.ssm_state, cfg.ssm_nheads, cfg.ssm_headdim
    w = cfg.ssm_conv_width
    return {
        "h": jnp.zeros((batch, nh, P, N), jnp.float32),
        "conv_x": jnp.zeros((batch, w - 1, di), dtype),
        "conv_B": jnp.zeros((batch, w - 1, N), dtype),
        "conv_C": jnp.zeros((batch, w - 1, N), dtype),
    }


def mamba_cache_abstract(cfg, batch: int, dtype=jnp.float32):
    di, N, nh, P = cfg.d_inner, cfg.ssm_state, cfg.ssm_nheads, cfg.ssm_headdim
    w = cfg.ssm_conv_width
    return {
        "h": jax.ShapeDtypeStruct((batch, nh, P, N), jnp.float32),
        "conv_x": jax.ShapeDtypeStruct((batch, w - 1, di), dtype),
        "conv_B": jax.ShapeDtypeStruct((batch, w - 1, N), dtype),
        "conv_C": jax.ShapeDtypeStruct((batch, w - 1, N), dtype),
    }


MAMBA_CACHE_AXES = {
    "h": ("batch", "ssm_heads", None, None),
    "conv_x": ("batch", None, "ssm_inner"),
    "conv_B": ("batch", None, "state"),
    "conv_C": ("batch", None, "state"),
}


def mamba_decode(params, cfg, x: jax.Array, cache: dict,
                 policy: ShardingPolicy) -> Tuple[jax.Array, dict]:
    """One-token decode.  x (B, d_model)."""
    B, _ = x.shape
    di, N, nh, P = cfg.d_inner, cfg.ssm_state, cfg.ssm_nheads, cfg.ssm_headdim
    dt_ = x.dtype
    xz = x @ params["w_z"].astype(dt_)
    xi = x @ params["w_x"].astype(dt_)
    Bm = x @ params["w_B"].astype(dt_)
    Cm = x @ params["w_C"].astype(dt_)
    dt = x @ params["w_dt"].astype(dt_)

    xi, cx = conv_step(xi, cache["conv_x"], params["conv_x"])
    Bm, cB = conv_step(Bm, cache["conv_B"], params["conv_B"])
    Cm, cC = conv_step(Cm, cache["conv_C"], params["conv_C"])
    xi, Bm, Cm = jax.nn.silu(xi), jax.nn.silu(Bm), jax.nn.silu(Cm)
    dt = jax.nn.softplus(dt.astype(jnp.float32) +
                         params["dt_bias"].astype(jnp.float32))
    A = -jnp.exp(params["A_log"].astype(jnp.float32))

    xh = xi.reshape(B, nh, P)
    y, h_new = ssd_decode_step(xh, dt, A, Bm, Cm, cache["h"])
    y = (y + params["D"].astype(jnp.float32)[None, :, None] * xh
         ).astype(dt_)
    y = y.reshape(B, di)
    y = rmsnorm({"scale": params["norm"]}, y * jax.nn.silu(xz), cfg.norm_eps)
    out = y @ params["w_out"].astype(dt_)
    return out, {"h": h_new, "conv_x": cx, "conv_B": cB, "conv_C": cC}
