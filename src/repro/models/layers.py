"""Core layers shared by every architecture in the zoo.

Design rules:
  * params are plain nested dicts of ``jnp.ndarray`` (f32 masters);
  * every param is declared through a ``ParamSpec`` carrying *logical* axis
    names, so sharding policies can map them to mesh axes without the layer
    knowing anything about meshes;
  * compute runs in ``compute_dtype`` (bf16 by default), masters stay f32;
  * attention is query-chunked above ``CHUNK_THRESHOLD`` so 32k-sequence
    prefill never materialises an (S × S) score tensor — the pure-JAX
    analogue of the flash-attention kernel in ``repro.kernels``.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.sharding.policy import ShardingPolicy, constrain

PyTree = Any

CHUNK_THRESHOLD = 8192     # chunk queries when S >= this
QUERY_CHUNK = 1024

# ---- §Perf hillclimb knobs (set by launch/dryrun --variant) ----------
# dtype the attention score/prob matrices materialise in.  f32 is the
# paper-faithful baseline; bf16 halves the dominant HBM term of the
# unfused attention path (the Pallas flash kernel keeps them in VMEM
# entirely — see EXPERIMENTS.md §Perf).
SCORE_DTYPE = jnp.float32
# sequence-chunked cross-entropy: when > 0 the (B, S, V) logit loss is
# computed in S/chunk pieces via lax.map, bounding live logits memory.
XENT_SEQ_CHUNK = 0
# GQA→MHA expansion: when KV heads do not divide the TP degree (deepseek
# kv=8, qwen2-vl kv=4 on a 16-way model axis), the 5-D grouped attention
# einsum defeats GSPMD propagation and the full (B,KV,rep,S,S) score
# tensor replicates per device with TiB-scale all-gathers.  Expanding K/V
# to the query-head count gives a 4-D head-sharded einsum GSPMD handles
# (pads 56→64 heads internally) — the standard Megatron/vLLM posture for
# KV < TP.
GQA_EXPAND = False
# cast-before-gather: convert the f32 master params to compute dtype ONCE,
# sharded, at step entry — so FSDP's per-layer all-gathers move bf16, not
# f32 (XLA does not reorder convert past all-gather on its own; halves the
# dominant collective term of the fsdp_all policy).
CAST_PARAMS_ONCE = False


def maybe_cast_params(params, dtype):
    if not CAST_PARAMS_ONCE:
        return params
    return jax.tree.map(
        lambda p: p.astype(dtype) if (hasattr(p, "dtype")
                                      and p.dtype == jnp.float32) else p,
        params)


# ======================================================================
# Param declaration
# ======================================================================
@dataclasses.dataclass(frozen=True)
class ParamSpec:
    shape: Tuple[int, ...]
    axes: Tuple[Optional[str], ...]          # logical axis names
    init: str = "normal"                     # normal | zeros | ones
    scale: Optional[float] = None            # stddev for "normal"
    # the model reads this leaf only as ``.astype(compute_dtype)``, so
    # its compute copy (``compute_params``) may hold it cast; leaves read
    # in f32 (norm scales, SSM decay terms, conv taps, router) stay False
    cast: bool = False

    def __post_init__(self):
        assert len(self.shape) == len(self.axes), (self.shape, self.axes)


def _leaf_paths(tree: PyTree, prefix=()):
    if isinstance(tree, dict):
        for k in sorted(tree):
            yield from _leaf_paths(tree[k], prefix + (k,))
    else:
        yield prefix, tree


def _stable_hash(s: str) -> int:
    """Process-independent string hash (Python's hash() is randomised by
    PYTHONHASHSEED — multi-host init must agree bitwise across processes)."""
    import zlib
    return zlib.crc32(s.encode()) & 0x7FFFFFFF


def init_params(specs: PyTree, key: jax.Array, dtype=jnp.float32) -> PyTree:
    """Materialise a param pytree from ParamSpecs (deterministic per path)."""
    def make(path, spec: ParamSpec):
        if spec.init == "zeros":
            return jnp.zeros(spec.shape, dtype)
        if spec.init == "ones":
            return jnp.ones(spec.shape, dtype)
        k = key
        for p in path:
            k = jax.random.fold_in(k, _stable_hash(p))
        scale = spec.scale
        if scale is None:
            fan_in = spec.shape[0] if len(spec.shape) >= 1 else 1
            scale = 1.0 / np.sqrt(max(1, fan_in))
        return (jax.random.normal(k, spec.shape, jnp.float32) * scale).astype(dtype)

    out = {}
    for path, spec in _leaf_paths(specs):
        node = out
        for p in path[:-1]:
            node = node.setdefault(p, {})
        node[path[-1]] = make(path, spec)
    return out


def abstract_params(specs: PyTree, dtype=jnp.float32) -> PyTree:
    """ShapeDtypeStruct pytree (no allocation) — used by the dry-run."""
    out = {}
    for path, spec in _leaf_paths(specs):
        node = out
        for p in path[:-1]:
            node = node.setdefault(p, {})
        node[path[-1]] = jax.ShapeDtypeStruct(spec.shape, dtype)
    return out


def axes_tree(specs: PyTree) -> PyTree:
    out = {}
    for path, spec in _leaf_paths(specs):
        node = out
        for p in path[:-1]:
            node = node.setdefault(p, {})
        node[path[-1]] = spec.axes
    return out


def stack_specs(specs: PyTree, n: int) -> PyTree:
    """Add a leading scan ("layers") dim of size n to every ParamSpec."""
    def f(s: ParamSpec) -> ParamSpec:
        return dataclasses.replace(s, shape=(n,) + s.shape,
                                   axes=("layers",) + s.axes)
    return jax.tree.map(f, specs,
                        is_leaf=lambda x: isinstance(x, ParamSpec))


@functools.partial(jax.jit, static_argnums=1)
def _astype(leaves, dtype):
    return [p.astype(dtype) for p in leaves]


def compute_params(specs: PyTree, params: PyTree, dtype) -> PyTree:
    """The tree a serving program takes: every leaf declared ``cast`` in
    ``dtype``, every other leaf the same array as in ``params``.

    The programs cast those leaves themselves, so the result computes
    bit for bit what ``params`` does; it only saves the cast.  A tree
    with nothing to cast (params already held in ``dtype``) comes back
    as itself: no copy is made."""
    dtype = jnp.dtype(dtype)
    marks = jax.tree.map(lambda s, p: s.cast and p.dtype != dtype,
                         specs, params,
                         is_leaf=lambda x: isinstance(x, ParamSpec))
    marks = jax.tree.leaves(marks)
    if not any(marks):
        return params
    leaves, treedef = jax.tree.flatten(params)
    cast = iter(_astype([p for p, m in zip(leaves, marks) if m], dtype))
    return jax.tree.unflatten(
        treedef, [next(cast) if m else p for p, m in zip(leaves, marks)])


# ======================================================================
# Normalisation
# ======================================================================
def rmsnorm_spec(d: int) -> Dict[str, ParamSpec]:
    return {"scale": ParamSpec((d,), ("d_model",), init="ones")}


def rmsnorm(params, x, eps: float = 1e-5):
    dtype = x.dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    y = x32 * jax.lax.rsqrt(var + eps)
    return (y * params["scale"].astype(jnp.float32)).astype(dtype)


def head_rmsnorm(scale, x, eps: float = 1e-5):
    """Per-head q/k norm (Qwen3): x (..., hd), scale (hd,)."""
    dtype = x.dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)).astype(dtype)


# ======================================================================
# Rotary embeddings (incl. multimodal M-RoPE)
# ======================================================================
def mrope_sections(head_dim: int) -> Tuple[int, int, int]:
    half = head_dim // 2
    s = 3 * half // 8
    return (half - 2 * s, s, s)          # e.g. hd=128 -> (16, 24, 24)


def rope_freqs(head_dim: int, theta: float) -> np.ndarray:
    half = head_dim // 2
    return 1.0 / (theta ** (np.arange(half, dtype=np.float64) / half))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float,
               mrope: bool = False) -> jax.Array:
    """x: (B, S, H, hd); positions: (B, S) or (3, B, S) for M-RoPE."""
    hd = x.shape[-1]
    half = hd // 2
    freqs = jnp.asarray(rope_freqs(hd, theta), jnp.float32)      # (half,)
    if mrope:
        # positions (3, B, S): temporal/height/width per frequency section
        sec = mrope_sections(hd)
        idx = np.concatenate([np.full(s, i) for i, s in enumerate(sec)])
        pos = positions.astype(jnp.float32)[idx]                 # (half, B, S)
        angles = jnp.einsum("hbs,h->bsh", pos, freqs)            # (B, S, half)
    else:
        angles = positions.astype(jnp.float32)[..., None] * freqs  # (B,S,half)
    cos = jnp.cos(angles)[:, :, None, :]                          # (B,S,1,half)
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ======================================================================
# Attention
# ======================================================================
def attention_specs(cfg) -> Dict[str, ParamSpec]:
    d, H, KV, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    s: Dict[str, ParamSpec] = {
        "wq": ParamSpec((d, H * hd), ("d_model", "heads"), cast=True),
        "wk": ParamSpec((d, KV * hd), ("d_model", "kv_heads"), cast=True),
        "wv": ParamSpec((d, KV * hd), ("d_model", "kv_heads"), cast=True),
        "wo": ParamSpec((H * hd, d), ("heads", "d_model"), cast=True),
    }
    if cfg.qkv_bias:
        s["bq"] = ParamSpec((H * hd,), ("heads",), init="zeros", cast=True)
        s["bk"] = ParamSpec((KV * hd,), ("kv_heads",), init="zeros",
                            cast=True)
        s["bv"] = ParamSpec((KV * hd,), ("kv_heads",), init="zeros",
                            cast=True)
    if cfg.qk_norm:
        s["q_norm"] = ParamSpec((hd,), ("head_dim",), init="ones")
        s["k_norm"] = ParamSpec((hd,), ("head_dim",), init="ones")
    return s


def _qkv(params, cfg, x, positions, policy: ShardingPolicy):
    """Project to q (B,S,H,hd), k/v (B,S,KV,hd), with RoPE applied
    unless the config has no positional encoding (``cfg.rope``)."""
    B, S, _ = x.shape
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    dt = x.dtype
    q = x @ params["wq"].astype(dt)
    k = x @ params["wk"].astype(dt)
    v = x @ params["wv"].astype(dt)
    if cfg.qkv_bias:
        q = q + params["bq"].astype(dt)
        k = k + params["bk"].astype(dt)
        v = v + params["bv"].astype(dt)
    q = q.reshape(B, S, H, hd)
    k = k.reshape(B, S, KV, hd)
    v = v.reshape(B, S, KV, hd)
    if cfg.qk_norm:
        q = head_rmsnorm(params["q_norm"], q, cfg.norm_eps)
        k = head_rmsnorm(params["k_norm"], k, cfg.norm_eps)
    if cfg.rope and positions is not None:
        q = apply_rope(q, positions, cfg.rope_theta, cfg.mrope)
        k = apply_rope(k, positions, cfg.rope_theta, cfg.mrope)
    q = constrain(q, policy, "batch", "seq", "heads", None)
    k = constrain(k, policy, "batch", "seq", "kv_heads", None)
    v = constrain(v, policy, "batch", "seq", "kv_heads", None)
    return q, k, v


def _sdpa_block(q, k, v, mask, scale):
    """q (B,Q,KV,rep,hd), k/v (B,Sk,KV,hd), mask (Q,Sk) bool or None."""
    scores = jnp.einsum("bqgrd,bkgd->bgrqk", q, k) * scale
    scores = scores.astype(SCORE_DTYPE)
    neg = jnp.asarray(-1e30 if SCORE_DTYPE == jnp.float32 else -3e38,
                      SCORE_DTYPE)
    if mask is not None:
        scores = jnp.where(mask[None, None, None], scores, neg)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bgrqk,bkgd->bqgrd", probs, v)


def maybe_expand_gqa(q, k, v, policy: ShardingPolicy):
    """GQA_EXPAND knob: broadcast K/V to the query-head count so attention
    shards on the (padded) head dim instead of the non-divisible KV dim."""
    H, KV = q.shape[2], k.shape[2]
    if not GQA_EXPAND or H == KV:
        return k, v
    rep = H // KV
    k = jnp.repeat(k, rep, axis=2)
    v = jnp.repeat(v, rep, axis=2)
    k = constrain(k, policy, "batch", "seq", "heads", None)
    v = constrain(v, policy, "batch", "seq", "heads", None)
    return k, v


def self_attention(q, k, v, *, causal: bool, window: int = 0,
                   q_offset: int = 0, scale: float = 0.0):
    """Exact chunked attention.  q (B,Sq,H,hd), k/v (B,Sk,KV,hd); scores
    are scaled by ``scale``, 0 meaning 1/sqrt(hd).

    Query chunking keeps the live score block at (Cq × Sk) instead of
    (Sq × Sk); with SWA the key block is additionally sliced to
    (window + Cq), making compute sub-quadratic.
    """
    B, Sq, H, hd = q.shape
    KV = k.shape[2]
    Sk = k.shape[1]
    rep = H // KV
    scale = scale or 1.0 / np.sqrt(hd)
    qg = q.reshape(B, Sq, KV, rep, hd)

    def mask_for(qpos, kpos):
        m = jnp.ones((qpos.shape[0], kpos.shape[0]), bool)
        if causal:
            m &= kpos[None, :] <= qpos[:, None]
        if window:
            m &= kpos[None, :] > qpos[:, None] - window
        return m

    if Sq < CHUNK_THRESHOLD or Sq % QUERY_CHUNK != 0:
        qpos = jnp.arange(Sq) + q_offset
        kpos = jnp.arange(Sk)
        mask = mask_for(qpos, kpos) if (causal or window) else None
        out = _sdpa_block(qg, k, v, mask, scale)
        return out.reshape(B, Sq, H, hd)

    # ---- chunked path (S >= CHUNK_THRESHOLD) ----
    nC = Sq // QUERY_CHUNK
    qc = qg.reshape(B, nC, QUERY_CHUNK, KV, rep, hd)

    use_window = window and window + QUERY_CHUNK < Sk

    def one_chunk(c, q_chunk):
        qpos = c * QUERY_CHUNK + jnp.arange(QUERY_CHUNK) + q_offset
        if use_window:
            blk = window + QUERY_CHUNK
            start = jnp.clip(c * QUERY_CHUNK + q_offset - window, 0, Sk - blk)
            kb = jax.lax.dynamic_slice_in_dim(k, start, blk, axis=1)
            vb = jax.lax.dynamic_slice_in_dim(v, start, blk, axis=1)
            kpos = start + jnp.arange(blk)
        else:
            kb, vb = k, v
            kpos = jnp.arange(Sk)
        m = mask_for(qpos, kpos) if (causal or window) else None
        return _sdpa_block(q_chunk, kb, vb, m, scale)

    out = jax.lax.map(lambda args: one_chunk(args[0], args[1]),
                      (jnp.arange(nC), jnp.moveaxis(qc, 1, 0)))
    out = jnp.moveaxis(out, 0, 1).reshape(B, Sq, H, hd)
    return out


def cross_attention(q, k, v):
    """Non-causal attention against a fixed memory (whisper cross-attn)."""
    return self_attention(q, k, v, causal=False, window=0)


def mask_padded_vocab(logits: jax.Array, cfg) -> jax.Array:
    """-inf out the vocab-padding columns (see ModelConfig.padded_vocab)."""
    if cfg.padded_vocab == cfg.vocab_size:
        return logits
    iota = jax.lax.broadcasted_iota(jnp.int32, logits.shape, logits.ndim - 1)
    return jnp.where(iota < cfg.vocab_size, logits,
                     jnp.asarray(-1e30, logits.dtype))


# ======================================================================
# loss
# ======================================================================
def _xent_nll(logits: jax.Array, targets: jax.Array) -> jax.Array:
    """Per-token NLL over a vocab-sharded logits tensor.

    The label logit is extracted with an iota-compare masked reduction
    instead of take_along_axis: a gather on the sharded vocab dim makes
    GSPMD all-gather the full (B, S, V) logits per device (tens of GB at
    150k vocab); compare+select+reduce stays sharded and fuses — the
    all-reduce is only the (B, S) partials.
    """
    lg = logits.astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(lg, axis=-1)              # (B,S)
    iota = jax.lax.broadcasted_iota(jnp.int32, lg.shape, lg.ndim - 1)
    sel = jnp.where(iota == targets[..., None], lg, 0.0)
    tgt = jnp.sum(sel, axis=-1)                                  # (B,S)
    return lse - tgt


def softmax_xent_sharded(logits: jax.Array, targets: jax.Array,
                         mask: Optional[jax.Array] = None):
    """Masked mean cross-entropy; optionally sequence-chunked (the
    XENT_SEQ_CHUNK knob) so at most (B, chunk, V) logit-loss intermediates
    are live at once.  Callers keep S divisible by passing full-length
    logits with a shifted mask (see LM.loss) rather than slicing to S-1."""
    S = logits.shape[1]
    C = XENT_SEQ_CHUNK
    if C and S > C and S % C == 0:
        nC = S // C
        lg = jnp.moveaxis(
            logits.reshape(logits.shape[0], nC, C, -1), 1, 0)
        tg = jnp.moveaxis(targets.reshape(targets.shape[0], nC, C), 1, 0)
        nll = jax.lax.map(lambda ab: _xent_nll(ab[0], ab[1]), (lg, tg))
        nll = jnp.moveaxis(nll, 0, 1).reshape(targets.shape)
    else:
        nll = _xent_nll(logits, targets)
    if mask is None:
        mask = jnp.ones(targets.shape, jnp.float32)
    mask = mask.astype(jnp.float32)
    ntok = jnp.maximum(mask.sum(), 1.0)
    return (nll * mask).sum() / ntok, ntok


# ======================================================================
# MLP (SwiGLU)
# ======================================================================
def mlp_specs(d: int, ff: int) -> Dict[str, ParamSpec]:
    return {
        "w_gate": ParamSpec((d, ff), ("d_model", "d_ff"), cast=True),
        "w_up": ParamSpec((d, ff), ("d_model", "d_ff"), cast=True),
        "w_down": ParamSpec((ff, d), ("d_ff", "d_model"), cast=True),
    }


def mlp(params, x, policy: ShardingPolicy):
    dt = x.dtype
    g = x @ params["w_gate"].astype(dt)
    u = x @ params["w_up"].astype(dt)
    h = jax.nn.silu(g) * u
    h = constrain(h, policy, "batch", "seq", "d_ff")
    return h @ params["w_down"].astype(dt)
