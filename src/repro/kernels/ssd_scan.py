"""Mamba2 SSD (state-space duality) chunked-scan Pallas TPU kernel.

TPU adaptation (DESIGN.md §2): the Tri Dao SSD GPU kernel relies on warp-
level parallel prefix products; on TPU we exploit the *sequential* grid
instead — the recurrent state h (P×N, f32) lives in VMEM scratch carried
across the innermost (chunk) grid dimension, while the intra-chunk work is
MXU matmuls per step:

    CB      = C · Bᵀ                 (Q×N)·(N×Q)  -> (Q,Q)
    y_intra = (CB ⊙ L) · (dt⊙x)      (Q,Q)·(Q,P)
    y_inter = decay_in ⊙ (C · hᵀ)    (Q,N)·(N,P)
    h_new   = exp(Σ dA) h + xᵀ·(decay_out⊙dt⊙B)   (P,Q)·(Q,N)

Grid: (batch, heads, nChunks) — chunks innermost/sequential.

Layout: every block's last two dims are (8,128)-aligned or whole, as
Mosaic requires.  x/y are head-major (B, nh, S, P) so a block is one
head's (Q, P) chunk; B/C stay (B, S, N) with (Q, N) blocks.  The
per-head scalar A never enters the kernel: the wrapper folds it into the
chunk-local cumulative log-decay ``cum = cumsum(dt·A)`` (an XLA scan over
Q), which the kernel reads as a column (Q, 1) and as a row (1, Q) — the
two orientations the intra-chunk decay matrix L[i,j] = exp(cum_i − cum_j)
needs, without an in-kernel transpose or cumsum.  dt comes as a column.
P (head dim, 64 for mamba2-2.7b) rides the MXU at half occupancy.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(x_ref, dt_ref, cc_ref, cr_ref, b_ref, c_ref,
                y_ref, hout_ref, h_ref, *, chunk: int):
    ic = pl.program_id(2)
    nc = pl.num_programs(2)

    @pl.when(ic == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    x = x_ref[0, 0].astype(jnp.float32)           # (Q, P)
    dt = dt_ref[0, 0]                             # (Q, 1)
    cum = cc_ref[0, 0]                            # (Q, 1) inclusive cumsum
    cum_row = cr_ref[0, 0]                        # (1, Q) same, as a row
    Bm = b_ref[0].astype(jnp.float32)             # (Q, N)
    Cm = c_ref[0].astype(jnp.float32)             # (Q, N)

    # Σ dA over the chunk, as an (1, N) row: Mosaic cannot broadcast a
    # (1, 1) value over sublanes and lanes at once, so take the last row
    # of the lane-broadcast column with a masked sublane sum
    last = jax.lax.broadcasted_iota(jnp.int32, Bm.shape, 0) == chunk - 1
    total = jnp.sum(jnp.where(last, cum, 0.0), axis=0, keepdims=True)
    decay_in = jnp.exp(cum)                       # chunk entry -> i

    # intra-chunk: L[i,j] = exp(cum_i - cum_j) for j <= i
    tri = (jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
           >= jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1))
    L = jnp.where(tri, jnp.exp(cum - cum_row), 0.0)   # (Q, Q)
    CB = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    y_intra = jax.lax.dot_general(CB * L, dt * x,
                                  (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)

    # inter-chunk: y_inter[i] = decay_in[i] * C[i] · h_prev
    h = h_ref[...]                                # (P, N)
    y_inter = decay_in * jax.lax.dot_general(
        Cm, h, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)       # (Q, P)

    y_ref[0, 0] = (y_intra + y_inter).astype(y_ref.dtype)

    # state update
    w = jnp.exp(total - cum) * dt * Bm            # decay_out⊙dt⊙B (Q, N)
    contrib = jax.lax.dot_general(x, w, (((0,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)
    h_ref[...] = h * jnp.exp(total) + contrib

    @pl.when(ic == nc - 1)
    def _emit_state():
        hout_ref[0, 0] = h_ref[...]


def ssd_scan(x: jax.Array, dt: jax.Array, A: jax.Array,
             Bm: jax.Array, Cm: jax.Array, *, chunk: int = 128,
             interpret: bool = False) -> Tuple[jax.Array, jax.Array]:
    """Pallas SSD.  Same contract as ``kernels.ref.ssd_ref``:

    x (B, S, nh, P); dt (B, S, nh) post-softplus; A (nh,) negative;
    Bm/Cm (B, S, N) -> y (B, S, nh, P), h_final (B, nh, P, N).

    S is padded to a chunk multiple with dt=0 no-op steps (decay 1,
    contribution 0) — semantics-preserving for the recurrence.
    """
    B, S, nh, P = x.shape
    N = Bm.shape[-1]
    Q = min(chunk, S)
    pad = (-S) % Q
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        Bm = jnp.pad(Bm, ((0, 0), (0, pad), (0, 0)))
        Cm = jnp.pad(Cm, ((0, 0), (0, pad), (0, 0)))
    S_pad = x.shape[1]
    nC = S_pad // Q

    # head-major layouts; the chunk-local cumulative log-decay replaces A
    xt = jnp.moveaxis(x, 2, 1)                                # (B,nh,S,P)
    dtt = jnp.moveaxis(dt.astype(jnp.float32), 2, 1)          # (B,nh,S)
    dA = dtt * A.astype(jnp.float32)[None, :, None]
    cum = jnp.cumsum(dA.reshape(B, nh, nC, Q), axis=-1).reshape(B, nh, S_pad)

    kernel = functools.partial(_ssd_kernel, chunk=Q)
    y, h_final = pl.pallas_call(
        kernel,
        grid=(B, nh, nC),
        in_specs=[
            pl.BlockSpec((1, 1, Q, P), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, Q, 1), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, Q, 1), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, 1, Q), lambda b, h, c: (b, h, 0, c)),
            pl.BlockSpec((1, Q, N), lambda b, h, c: (b, c, 0)),
            pl.BlockSpec((1, Q, N), lambda b, h, c: (b, c, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, Q, P), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, P, N), lambda b, h, c: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, nh, S_pad, P), x.dtype),
            jax.ShapeDtypeStruct((B, nh, P, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],  # carried state
        interpret=interpret,
    )(xt, dtt[..., None], cum[..., None], cum[:, :, None, :], Bm, Cm)

    y = jnp.moveaxis(y, 1, 2)
    return (y[:, :S] if pad else y), h_final
