"""Flash attention Pallas TPU kernel (forward).

TPU adaptation notes (DESIGN.md §2): the GPU flash-attention algorithm is
re-blocked for the TPU memory hierarchy — q/k/v tiles stream HBM→VMEM via
``BlockSpec`` index maps, the running softmax state (m, l, acc) lives in
VMEM scratch that persists across the *sequential* innermost grid dimension
(TPU grids execute in order, the Pallas analogue of a k-loop), and all
matmul tile dims are multiples of the 128-wide MXU systolic array.

Grid: (batch, q_heads, nQ, nK) — nK innermost/sequential.
GQA is folded into the k/v ``index_map`` (kv head = h * KV // H), so k/v
tiles are fetched once per kv-head and reused by the query-head group.

Causal + sliding-window masking is positional (absolute q/k positions via
``broadcasted_iota``); fully-masked k-blocks are skipped with ``pl.when``
(block-sparse early-out, halves causal work).
"""
from __future__ import annotations

import functools
import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref,
                  acc_ref, m_ref, l_ref, *,
                  scale: float, causal: bool, window: int,
                  block_q: int, block_k: int, seq_k: int):
    """One (q-block, k-block) step of the online-softmax recurrence."""
    iq = pl.program_id(2)
    ik = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q_start = iq * block_q
    k_start = ik * block_k

    # --- block-level early-out -----------------------------------------
    # causal: skip k-blocks entirely above the diagonal;
    # window:  skip k-blocks entirely below the window of the last query.
    run = jnp.bool_(True)
    if causal:
        run &= k_start <= q_start + block_q - 1
    if window:
        run &= k_start + block_k - 1 > q_start - window

    @pl.when(run)
    def _step():
        q = q_ref[0, 0].astype(jnp.float32)              # (bq, hd)
        k = k_ref[0, 0].astype(jnp.float32)              # (bk, hd)
        v = v_ref[0, 0].astype(jnp.float32)              # (bk, hd)

        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # (bq, bk)

        qpos = q_start + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        kpos = k_start + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        mask = kpos < seq_k                               # key padding
        if causal:
            mask &= kpos <= qpos
        if window:
            mask &= kpos > qpos - window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]                               # (bq, 1)
        l_prev = l_ref[...]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)                   # rescale old acc
        p = jnp.exp(s - m_new)                            # (bq, bk)
        l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)

        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new
        l_ref[...] = l_new

    @pl.when(ik == nk - 1)
    def _finalize():
        l = l_ref[...]
        # fully-masked rows (can happen only in key padding) -> 0
        safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_ref[...] / safe).astype(o_ref.dtype)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: int = 0,
                    block_q: int = 512, block_k: int = 512,
                    interpret: bool = False) -> jax.Array:
    """q (B, Sq, H, hd); k/v (B, Sk, KV, hd) -> (B, Sq, H, hd).

    Sq/Sk are padded to the block sizes; padded keys are masked, padded
    queries sliced off.  hd must be 128-aligned for MXU efficiency on real
    TPUs (validated in interpret mode regardless).
    """
    B, Sq, H, hd = q.shape
    Sk, KV = k.shape[1], k.shape[2]
    assert H % KV == 0, (H, KV)
    scale = 1.0 / np.sqrt(hd)

    block_q = min(block_q, max(Sq, 16))
    block_k = min(block_k, max(Sk, 16))
    pq = (-Sq) % block_q
    pk = (-Sk) % block_k
    qp = jnp.pad(q, ((0, 0), (0, pq), (0, 0), (0, 0))) if pq else q
    kp = jnp.pad(k, ((0, 0), (0, pk), (0, 0), (0, 0))) if pk else k
    vp = jnp.pad(v, ((0, 0), (0, pk), (0, 0), (0, 0))) if pk else v

    # (B, S, H, hd) -> (B, H, S, hd) for contiguous per-head tiles
    qt = jnp.moveaxis(qp, 2, 1)
    kt = jnp.moveaxis(kp, 2, 1)
    vt = jnp.moveaxis(vp, 2, 1)

    nQ = qt.shape[2] // block_q
    nK = kt.shape[2] // block_k
    grid = (B, H, nQ, nK)

    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal, window=window,
        block_q=block_q, block_k=block_k, seq_k=Sk)

    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, hd),
                         lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, block_k, hd),
                         lambda b, h, iq, ik, KV=KV, H=H:
                         (b, h * KV // H, ik, 0)),
            pl.BlockSpec((1, 1, block_k, hd),
                         lambda b, h, iq, ik, KV=KV, H=H:
                         (b, h * KV // H, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, hd),
                               lambda b, h, iq, ik: (b, h, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, qt.shape[2], hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, hd), jnp.float32),     # acc
            pltpu.VMEM((block_q, 1), jnp.float32),      # m (running max)
            pltpu.VMEM((block_q, 1), jnp.float32),      # l (running denom)
        ],
        interpret=interpret,
    )(qt, kt, vt)

    out = jnp.moveaxis(out, 1, 2)
    return out[:, :Sq] if pq else out

