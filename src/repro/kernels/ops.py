"""Jit'd dispatch layer over the Pallas kernels.

On a TPU backend the kernels lower through Mosaic; on any other backend
they execute with ``interpret=True`` — the kernel body runs op-by-op with
identical semantics, which is how the per-kernel allclose tests validate
them on the CPU.  The backend alone decides.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax

from repro.kernels import flash_attention as _fa
from repro.kernels import rmsnorm as _rn
from repro.kernels import ssd_scan as _ssd


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _attention_vjp(q, k, v, causal, window, block_q, block_k):
    return _fa.flash_attention(q, k, v, causal=causal, window=window,
                               block_q=block_q, block_k=block_k,
                               interpret=_interpret())


def _attention_fwd(q, k, v, causal, window, block_q, block_k):
    return _attention_vjp(q, k, v, causal, window, block_q, block_k), \
        (q, k, v)


def _attention_bwd(causal, window, block_q, block_k, res, g):
    """Backward through the exact reference (the standard fast-forward
    pattern: Pallas fwd kernel + XLA-differentiated bwd — bitwise-matched
    to the oracle in tests)."""
    from repro.kernels.ref import attention_ref
    q, k, v = res
    _, vjp = jax.vjp(
        lambda q_, k_, v_: attention_ref(q_, k_, v_, causal=causal,
                                         window=window), q, k, v)
    return vjp(g)


_attention_vjp.defvjp(_attention_fwd, _attention_bwd)


@functools.partial(jax.jit, static_argnames=("causal", "window",
                                             "block_q", "block_k"))
def attention(q, k, v, *, causal: bool = True, window: int = 0,
              block_q: int = 512, block_k: int = 512) -> jax.Array:
    """Flash attention.  q (B,Sq,H,hd), k/v (B,Sk,KV,hd).  Differentiable
    (custom VJP: kernel forward, reference backward)."""
    return _attention_vjp(q, k, v, causal, window, block_q, block_k)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _ssd_vjp(x, dt, A, Bm, Cm, chunk):
    return _ssd.ssd_scan(x, dt, A, Bm, Cm, chunk=chunk,
                         interpret=_interpret())


def _ssd_fwd(x, dt, A, Bm, Cm, chunk):
    return _ssd_vjp(x, dt, A, Bm, Cm, chunk), (x, dt, A, Bm, Cm)


def _ssd_bwd(chunk, res, g):
    from repro.models.mamba import ssd_chunked
    x, dt, A, Bm, Cm = res
    _, vjp = jax.vjp(lambda *a: ssd_chunked(*a, chunk=chunk),
                     x, dt, A, Bm, Cm)
    return vjp(g)


_ssd_vjp.defvjp(_ssd_fwd, _ssd_bwd)


@functools.partial(jax.jit, static_argnames=("chunk",))
def ssd(x, dt, A, Bm, Cm, *, chunk: int = 128
        ) -> Tuple[jax.Array, jax.Array]:
    """Chunked SSD scan (differentiable: kernel fwd, chunked-jnp bwd)."""
    return _ssd_vjp(x, dt, A, Bm, Cm, chunk)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _rmsnorm_vjp(x, scale, eps, block_rows):
    return _rn.rmsnorm(x, scale, eps=eps, block_rows=block_rows,
                       interpret=_interpret())


def _rmsnorm_fwd(x, scale, eps, block_rows):
    return _rmsnorm_vjp(x, scale, eps, block_rows), (x, scale)


def _rmsnorm_bwd(eps, block_rows, res, g):
    from repro.kernels.ref import rmsnorm_ref
    x, scale = res
    _, vjp = jax.vjp(lambda x_, s_: rmsnorm_ref(x_, s_, eps), x, scale)
    return vjp(g)


_rmsnorm_vjp.defvjp(_rmsnorm_fwd, _rmsnorm_bwd)


@functools.partial(jax.jit, static_argnames=("eps", "block_rows"))
def rmsnorm(x, scale, *, eps: float = 1e-5,
            block_rows: int = 256) -> jax.Array:
    return _rmsnorm_vjp(x, scale, eps, block_rows)
