"""Unified language model covering the dense / MoE / SSM / hybrid / VLM
architectures in the zoo.

Layers are stacked per *pattern position* and iterated with
``jax.lax.scan`` over super-blocks (one super-block = one cycle of
``cfg.layer_pattern``), with full activation rematerialisation per block —
this keeps the HLO compact enough to compile 94-layer models on a
512-device mesh and is the standard memory/recompute trade at scale.

Batch dict keys (all optional except "tokens"):
  tokens         (B, S) int32
  loss_mask      (B, S) f32/bool — 1 where the next-token loss applies
  positions      (B, S) or (3, B, S) int32 (M-RoPE)
  vision_embeds  (B, P, d) — VLM stub frontend output, overrides the first
                 P token embeddings
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import layers as L
from repro.models import mamba as M
from repro.models import moe as MOE
from repro.models.config import ModelConfig
from repro.sharding.policy import ShardingPolicy, constrain

PyTree = Any


# ======================================================================
# per-layer specs
# ======================================================================
def _layer_specs(cfg: ModelConfig, pos: int) -> Dict[str, Any]:
    kind = cfg.layer_pattern[pos % len(cfg.layer_pattern)]
    s: Dict[str, Any] = {
        "pre_mixer_norm": L.rmsnorm_spec(cfg.d_model),
        "pre_mlp_norm": L.rmsnorm_spec(cfg.d_model),
    }
    if kind in ("attn", "swa"):
        s["attn"] = L.attention_specs(cfg)
    else:
        s["mamba"] = M.mamba_specs(cfg)
    if cfg.is_moe_layer(pos):
        s["moe"] = MOE.moe_specs(cfg)
    elif cfg.d_ff > 0:
        s["mlp"] = L.mlp_specs(cfg.d_model, cfg.d_ff)
    return s


def lm_param_specs(cfg: ModelConfig) -> Dict[str, Any]:
    P = len(cfg.layer_pattern)
    assert cfg.num_layers % P == 0, (cfg.num_layers, P)
    n_sb = cfg.num_layers // P
    blocks = {f"pos{j}": L.stack_specs(_layer_specs(cfg, j), n_sb)
              for j in range(P)}
    specs: Dict[str, Any] = {
        "embed": {"tok": L.ParamSpec((cfg.padded_vocab, cfg.d_model),
                                     ("vocab", "d_model"), scale=0.02,
                                     cast=True)},
        "blocks": blocks,
        "final_norm": L.rmsnorm_spec(cfg.d_model),
    }
    if not cfg.tie_embeddings:
        specs["lm_head"] = L.ParamSpec((cfg.d_model, cfg.padded_vocab),
                                       ("d_model", "vocab"), cast=True)
    return specs


# ======================================================================
# blocks
# ======================================================================
def _mixer(lp, cfg, kind, x, positions, policy, use_kernels=False):
    if kind in ("attn", "swa"):
        q, k, v = L._qkv(lp["attn"], cfg, x, positions, policy)
        k, v = L.maybe_expand_gqa(q, k, v, policy)
        window = cfg.sliding_window if kind == "swa" else 0
        if use_kernels:
            from repro.kernels import ops
            if cfg.attn_scale:           # the kernel scales by 1/sqrt(hd)
                q = q * (cfg.attn_scale * np.sqrt(cfg.head_dim))
            o = ops.attention(q, k, v, causal=True, window=window)
        else:
            o = L.self_attention(q, k, v, causal=True, window=window,
                                 scale=cfg.attn_scale)
        B, S = x.shape[:2]
        o = o.reshape(B, S, cfg.num_heads * cfg.head_dim)
        return o @ lp["attn"]["wo"].astype(x.dtype)
    return M.mamba_block(lp["mamba"], cfg, x, policy, use_kernels=use_kernels)


def _residual(cfg, x, branch):
    """``x`` plus a residual branch, scaled by the config's multiplier."""
    if cfg.residual_multiplier != 1.0:
        branch = branch * cfg.residual_multiplier
    return x + branch


def _ffn(lp, cfg, pos, x, policy, mesh, dropless=False):
    if "moe" in lp:
        return MOE.moe_block(lp["moe"], cfg, x, policy, mesh,
                             dropless=dropless)
    if "mlp" in lp:
        return L.mlp(lp["mlp"], x, policy), jnp.zeros((), jnp.float32)
    return None, jnp.zeros((), jnp.float32)   # pure-SSM archs: no FFN


def _block(lp, cfg, pos, x, positions, policy, mesh, use_kernels=False):
    kind = cfg.layer_kind(pos)
    h = L.rmsnorm(lp["pre_mixer_norm"], x, cfg.norm_eps)
    x = _residual(cfg, x, _mixer(lp, cfg, kind, h, positions, policy,
                                 use_kernels))
    x = constrain(x, policy, "batch", "seq", "act_d")
    f, aux = _ffn(lp, cfg, pos,
                  L.rmsnorm(lp["pre_mlp_norm"], x, cfg.norm_eps),
                  policy, mesh)
    if f is not None:
        x = _residual(cfg, x, f)
        x = constrain(x, policy, "batch", "seq", "act_d")
    return x, aux


# ======================================================================
# model
# ======================================================================
class LM:
    def __init__(self, cfg: ModelConfig, policy: ShardingPolicy, mesh,
                 compute_dtype=jnp.bfloat16, param_dtype=jnp.float32,
                 remat: bool = True, use_kernels: bool = False):
        self.cfg = cfg
        self.policy = policy.for_mesh(mesh) if mesh is not None else policy
        self.mesh = mesh
        self.compute_dtype = compute_dtype
        self.param_dtype = param_dtype
        self.remat = remat
        self.use_kernels = use_kernels
        self._specs = lm_param_specs(cfg)

    # ---------------- params ----------------
    def init(self, key) -> PyTree:
        return L.init_params(self._specs, key, self.param_dtype)

    def init_abstract(self) -> PyTree:
        return L.abstract_params(self._specs, self.param_dtype)

    def param_axes(self) -> PyTree:
        return L.axes_tree(self._specs)

    def compute_params(self, params) -> PyTree:
        """``params`` as the serving programs take them: matrices, biases
        and the embedding in the compute dtype, the rest as held."""
        return L.compute_params(self._specs, params, self.compute_dtype)

    def param_shardings(self):
        ax = self.param_axes()
        return jax.tree.map(
            lambda a: self.policy.sharding(self.mesh, *a), ax,
            is_leaf=lambda x: isinstance(x, tuple))

    # ---------------- embedding / head ----------------
    def _embed_tokens(self, params, tokens):
        emb = jnp.take(params["embed"]["tok"].astype(self.compute_dtype),
                       tokens, axis=0)
        if self.cfg.embedding_multiplier != 1.0:
            emb = emb * self.cfg.embedding_multiplier
        return emb

    def _embed(self, params, batch):
        emb = self._embed_tokens(params, batch["tokens"])
        ve = batch.get("vision_embeds")
        if ve is not None:
            emb = jax.lax.dynamic_update_slice_in_dim(
                emb, ve.astype(self.compute_dtype), 0, axis=1)
        return constrain(emb, self.policy, "batch", "seq", "act_d")

    def _head(self, params, x):
        if self.cfg.tie_embeddings:
            w = params["embed"]["tok"].astype(x.dtype).T
        else:
            w = params["lm_head"].astype(x.dtype)
        logits = x @ w
        if self.cfg.logits_scaling != 1.0:
            logits = logits / self.cfg.logits_scaling
        logits = L.mask_padded_vocab(logits, self.cfg)
        return constrain(logits, self.policy, "batch", "logit_seq", "vocab")

    def _positions(self, batch):
        tokens = batch["tokens"]
        pos = batch.get("positions")
        if pos is not None:
            return pos
        B, S = tokens.shape
        base = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
        if self.cfg.mrope:
            return jnp.broadcast_to(base, (3, B, S))
        return base

    # ---------------- forward (train / prefill) ----------------
    def forward(self, params, batch) -> jax.Array:
        cfg = self.cfg
        params = L.maybe_cast_params(params, self.compute_dtype)
        x = self._embed(params, batch)
        positions = self._positions(batch)
        P = len(cfg.layer_pattern)

        def superblock(carry, block_params):
            x, aux = carry
            for j in range(P):
                x, a = _block(block_params[f"pos{j}"], cfg, j, x, positions,
                              self.policy, self.mesh, self.use_kernels)
                aux = aux + a
            return (x, aux), None

        body = superblock
        if self.remat:
            body = jax.checkpoint(superblock, prevent_cse=False)
        (x, aux), _ = jax.lax.scan(body,
                                   (x, jnp.zeros((), jnp.float32)),
                                   params["blocks"])
        x = L.rmsnorm(params["final_norm"], x, cfg.norm_eps)
        logits = self._head(params, x)
        self._last_aux = aux   # stashed for loss (retrieved within same trace)
        return logits

    def loss(self, params, batch) -> Tuple[jax.Array, Dict[str, jax.Array]]:
        logits = self.forward(params, batch)
        aux = self._last_aux
        tokens = batch["tokens"]
        # full-length next-token loss: targets = roll(tokens), final
        # position masked — keeps S (and its sharding/chunking) intact
        # instead of slicing to S-1.
        targets = jnp.roll(tokens, -1, axis=1)
        mask = batch.get("loss_mask")
        if mask is None:
            mask = jnp.ones(tokens.shape, jnp.float32)
        mask = mask.astype(jnp.float32).at[:, -1].set(0.0)
        loss, ntok = L.softmax_xent_sharded(logits, targets, mask)
        total = loss + 0.01 * aux
        return total, {"loss": loss, "aux_loss": aux, "ntokens": ntok}

    # ---------------- KV / SSM cache ----------------
    def _layer_cache_struct(self, pos: int, batch: int, max_seq: int,
                            abstract: bool):
        cfg = self.cfg
        kind = cfg.layer_kind(pos)
        mk = (lambda s, d: jax.ShapeDtypeStruct(s, d)) if abstract else \
             (lambda s, d: jnp.zeros(s, d))
        if kind in ("attn", "swa"):
            S = min(max_seq, cfg.sliding_window) if kind == "swa" else max_seq
            shp = (batch, S, cfg.num_kv_heads, cfg.head_dim)
            return {"k": mk(shp, self.compute_dtype),
                    "v": mk(shp, self.compute_dtype)}
        if abstract:
            return M.mamba_cache_abstract(cfg, batch, self.compute_dtype)
        return M.mamba_cache_init(cfg, batch, self.compute_dtype)

    def _cache(self, batch: int, max_seq: int, abstract: bool):
        cfg = self.cfg
        P = len(cfg.layer_pattern)
        n_sb = cfg.num_layers // P
        out = {}
        for j in range(P):
            leaf = self._layer_cache_struct(j, batch, max_seq, abstract)
            if abstract:
                out[f"pos{j}"] = jax.tree.map(
                    lambda s: jax.ShapeDtypeStruct((n_sb,) + s.shape, s.dtype),
                    leaf)
            else:
                out[f"pos{j}"] = jax.tree.map(
                    lambda a: jnp.broadcast_to(a, (n_sb,) + a.shape).copy(),
                    leaf)
        return out

    def init_cache(self, batch: int, max_seq: int):
        return self._cache(batch, max_seq, abstract=False)

    def cache_abstract(self, batch: int, max_seq: int):
        return self._cache(batch, max_seq, abstract=True)

    def cache_kinds(self) -> PyTree:
        """What each cache leaf holds, by its layer's kind: "kv" (keys
        and values, a row per position) or "state" (recurrent state and
        convolution tails, of a fixed size)."""
        cfg = self.cfg
        out = {}
        for j in range(len(cfg.layer_pattern)):
            if cfg.layer_kind(j) in ("attn", "swa"):
                out[f"pos{j}"] = {"k": "kv", "v": "kv"}
            else:
                out[f"pos{j}"] = {k: "state" for k in M.MAMBA_CACHE_AXES}
        return out

    def cache_axes(self) -> PyTree:
        cfg = self.cfg
        out = {}
        for j in range(len(cfg.layer_pattern)):
            kind = cfg.layer_kind(j)
            if kind in ("attn", "swa"):
                ax = {"k": ("layers", "batch", "cache_seq", "kv_heads", None),
                      "v": ("layers", "batch", "cache_seq", "kv_heads", None)}
            else:
                ax = {k: ("layers",) + v
                      for k, v in M.MAMBA_CACHE_AXES.items()}
            out[f"pos{j}"] = ax
        return out

    def cache_shardings(self, batch: Optional[int] = None,
                        max_seq: Optional[int] = None):
        """Decode-cache shardings.  Batch-aware: when the global batch does
        not divide the DP extent (e.g. long_500k, batch=1) the cache cannot
        shard its batch dim — shard the cache *sequence* dim over the DP
        axes instead (the long-context decode posture).  When ``max_seq``
        is also given, every spec is divisibility-fitted to the concrete
        cache shapes (e.g. 8 kv-heads on a 16-way TP axis replicate)."""
        from repro.sharding.policy import fit_shardings_tree
        ax = self.cache_axes()
        policy = _cache_policy(self.policy, self.mesh, batch)
        sh = jax.tree.map(
            lambda a: policy.sharding(self.mesh, *a), ax,
            is_leaf=lambda x: isinstance(x, tuple))
        if batch is not None and max_seq is not None:
            sh = fit_shardings_tree(sh, self.cache_abstract(batch, max_seq),
                                    self.mesh)
        return sh

    # ---------------- decode ----------------
    def _decode_attn(self, lp, kind, x, cache, pos):
        """x (B, d); cache {"k","v"} (B, S_c, KV, hd); pos scalar."""
        cfg = self.cfg
        B = x.shape[0]
        H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
        S_c = cache["k"].shape[1]
        window = cfg.sliding_window if kind == "swa" else 0

        posv = jnp.full((B, 1), pos, jnp.int32)
        if cfg.mrope:
            posv = jnp.broadcast_to(posv, (3, B, 1))
        q, k_new, v_new = L._qkv(lp["attn"], cfg, x[:, None, :], posv,
                                 self.policy)
        slot = jnp.mod(pos, S_c) if window else pos
        k = jax.lax.dynamic_update_slice_in_dim(cache["k"], k_new, slot, 1)
        v = jax.lax.dynamic_update_slice_in_dim(cache["v"], v_new, slot, 1)

        qg = q.reshape(B, 1, KV, H // KV, hd)
        scores = jnp.einsum("bqgrd,bkgd->bgrqk", qg, k)
        if cfg.attn_scale:
            scores = scores * cfg.attn_scale
        else:
            scores = scores / np.sqrt(hd)
        scores = scores.astype(jnp.float32)
        idx = jnp.arange(S_c)
        if window:
            valid = idx < jnp.minimum(pos + 1, S_c)       # ring buffer
        else:
            valid = idx <= pos
        scores = jnp.where(valid[None, None, None, None, :], scores, -1e30)
        probs = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
        o = jnp.einsum("bgrqk,bkgd->bqgrd", probs, v).reshape(B, H * hd)
        out = o @ lp["attn"]["wo"].astype(x.dtype)
        return out, {"k": k, "v": v}

    def decode_step(self, params, cache, tokens, pos
                    ) -> Tuple[jax.Array, PyTree]:
        """One serving step: tokens (B,) int32, pos scalar int32."""
        cfg = self.cfg
        P = len(cfg.layer_pattern)
        x = self._embed_tokens(params, tokens)             # (B, d)
        x = constrain(x, self.policy, "batch", "act_d")

        def superblock(x, xs):
            block_params, block_cache = xs
            new_cache = {}
            for j in range(P):
                lp = block_params[f"pos{j}"]
                lc = block_cache[f"pos{j}"]
                kind = cfg.layer_kind(j)
                h = L.rmsnorm(lp["pre_mixer_norm"], x, cfg.norm_eps)
                if kind in ("attn", "swa"):
                    with jax.named_scope("attn"):
                        o, nc = self._decode_attn(lp, kind, h, lc, pos)
                else:
                    with jax.named_scope("mamba"):
                        o, nc = M.mamba_decode(lp["mamba"], cfg, h, lc,
                                               self.policy)
                x = _residual(cfg, x, o)
                h2 = L.rmsnorm(lp["pre_mlp_norm"], x, cfg.norm_eps)
                if "moe" in lp:
                    f, _ = MOE.moe_block(lp["moe"], cfg, h2[:, None, :],
                                         self.policy, self.mesh,
                                         dropless=True)
                    x = _residual(cfg, x, f[:, 0, :])
                elif "mlp" in lp:
                    x = _residual(cfg, x, L.mlp(lp["mlp"], h2, self.policy))
                new_cache[f"pos{j}"] = nc
            return x, new_cache

        x, new_cache = jax.lax.scan(superblock, x,
                                    (params["blocks"], cache))
        x = L.rmsnorm(params["final_norm"], x, cfg.norm_eps)
        logits = self._head(params, x)
        return logits, new_cache

    # ---------------- prefill (build cache + logits) ----------------
    def _prefill_attn(self, lp, kind, h, positions):
        """Causal attention over the prompt, and its KV cache."""
        cfg = self.cfg
        q, k, v = L._qkv(lp["attn"], cfg, h, positions, self.policy)
        window = cfg.sliding_window if kind == "swa" else 0
        o = L.self_attention(q, k, v, causal=True, window=window,
                             scale=cfg.attn_scale)
        B, S = h.shape[:2]
        o = o.reshape(B, S, cfg.num_heads * cfg.head_dim)
        o = o @ lp["attn"]["wo"].astype(h.dtype)
        if window and window < k.shape[1]:
            # ring-buffer alignment: abs position p lives at slot p % window
            s = k.shape[1] % window
            return o, {"k": jnp.roll(k[:, -window:], s, axis=1),
                       "v": jnp.roll(v[:, -window:], s, axis=1)}
        return o, {"k": k, "v": v}

    def prefill(self, params, batch) -> Tuple[jax.Array, PyTree]:
        """Forward over a prompt, returning last-position logits and the
        populated KV/SSM cache (cache length == prompt length)."""
        cfg = self.cfg
        x = self._embed(params, batch)
        positions = self._positions(batch)
        P = len(cfg.layer_pattern)

        def superblock(carry, block_params):
            x = carry
            new_cache = {}
            for j in range(P):
                lp = block_params[f"pos{j}"]
                kind = cfg.layer_kind(j)
                h = L.rmsnorm(lp["pre_mixer_norm"], x, cfg.norm_eps)
                if kind in ("attn", "swa"):
                    with jax.named_scope("attn"):
                        o, nc = self._prefill_attn(lp, kind, h, positions)
                else:
                    with jax.named_scope("mamba"):
                        o, hfin, tails = _mamba_prefill(lp["mamba"], cfg, h,
                                                        self.policy)
                    nc = {"h": hfin, **tails}
                x = _residual(cfg, x, o)
                h2 = L.rmsnorm(lp["pre_mlp_norm"], x, cfg.norm_eps)
                # a serving prefill drops no token, as decode_step does not
                f, _ = _ffn(lp, cfg, j, h2, self.policy, self.mesh,
                            dropless=True)
                if f is not None:
                    x = _residual(cfg, x, f)
                new_cache[f"pos{j}"] = nc
            return x, new_cache

        body = superblock
        if self.remat:
            body = jax.checkpoint(superblock, prevent_cse=False)
        x, cache = jax.lax.scan(body, x, params["blocks"])
        x = L.rmsnorm(params["final_norm"], x[:, -1:, :], cfg.norm_eps)
        logits = self._head(params, x)[:, 0, :]
        return logits, cache


def _cache_policy(policy: ShardingPolicy, mesh, batch: Optional[int]
                  ) -> ShardingPolicy:
    """Pick batch- vs. sequence-sharding for the decode cache."""
    import dataclasses as _dc
    if batch is None or mesh is None:
        return policy
    dp = tuple(a for a in policy.dp if a in mesh.axis_names)
    dp_size = int(np.prod([mesh.shape[a] for a in dp])) if dp else 1
    if dp_size > 1 and batch % dp_size == 0:
        # batch shards cleanly: keep it, drop seq sharding (axis conflict)
        return _dc.replace(policy, shard_seq_decode=False)
    # batch unshardable: give the DP axes to the cache sequence dim
    return _dc.replace(policy, dp=(), seq=dp, shard_seq_decode=True)


def _mamba_prefill(params, cfg, x, policy):
    """Mamba forward that also returns the final SSM state (for prefill)."""
    B, S, _ = x.shape
    di, N, nh, Pdim = cfg.d_inner, cfg.ssm_state, cfg.ssm_nheads, cfg.ssm_headdim
    dt_ = x.dtype
    xz = x @ params["w_z"].astype(dt_)
    xi = x @ params["w_x"].astype(dt_)
    Bm = x @ params["w_B"].astype(dt_)
    Cm = x @ params["w_C"].astype(dt_)
    dt = x @ params["w_dt"].astype(dt_)
    w = cfg.ssm_conv_width
    tails = {"conv_x": xi[:, S - (w - 1):, :],
             "conv_B": Bm[:, S - (w - 1):, :],
             "conv_C": Cm[:, S - (w - 1):, :]}
    xi = jax.nn.silu(M.causal_conv(xi, params["conv_x"]))
    Bm = jax.nn.silu(M.causal_conv(Bm, params["conv_B"]))
    Cm = jax.nn.silu(M.causal_conv(Cm, params["conv_C"]))
    dt = jax.nn.softplus(dt.astype(jnp.float32) +
                         params["dt_bias"].astype(jnp.float32))
    A = -jnp.exp(params["A_log"].astype(jnp.float32))
    xh = xi.reshape(B, S, nh, Pdim)
    y, h_final = M.ssd_chunked(xh, dt, A, Bm, Cm, chunk=cfg.ssm_chunk)
    y = (y + params["D"].astype(jnp.float32)[None, None, :, None] * xh
         ).astype(dt_)
    y = y.reshape(B, S, di)
    y = L.rmsnorm({"scale": params["norm"]}, y * jax.nn.silu(xz),
                  cfg.norm_eps)
    return y @ params["w_out"].astype(dt_), h_final, tails
