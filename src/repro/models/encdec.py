"""Encoder-decoder model (Whisper-family backbone).

The audio conv frontend is a STUB per the assignment: the batch provides
post-conv *frame embeddings* (B, F, d_model).  The encoder is non-causal
self-attention; the decoder is a causal LM with cross-attention into the
encoder output.  Adaptations vs. the original Whisper (recorded in
DESIGN.md): RoPE instead of learned absolute positions, SwiGLU MLPs shared
with the rest of the zoo.

Batch keys: frames (B, F, d) f32/bf16, tokens (B, S) int32,
            loss_mask optional.
"""
from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import layers as L
from repro.models.config import ModelConfig
from repro.sharding.policy import ShardingPolicy, constrain

PyTree = Any


def _enc_layer_specs(cfg) -> Dict[str, Any]:
    return {
        "pre_attn_norm": L.rmsnorm_spec(cfg.d_model),
        "pre_mlp_norm": L.rmsnorm_spec(cfg.d_model),
        "attn": L.attention_specs(cfg),
        "mlp": L.mlp_specs(cfg.d_model, cfg.d_ff),
    }


def _dec_layer_specs(cfg) -> Dict[str, Any]:
    return {
        "pre_self_norm": L.rmsnorm_spec(cfg.d_model),
        "pre_cross_norm": L.rmsnorm_spec(cfg.d_model),
        "pre_mlp_norm": L.rmsnorm_spec(cfg.d_model),
        "self_attn": L.attention_specs(cfg),
        "cross_attn": L.attention_specs(cfg),
        "mlp": L.mlp_specs(cfg.d_model, cfg.d_ff),
    }


def encdec_param_specs(cfg: ModelConfig) -> Dict[str, Any]:
    specs: Dict[str, Any] = {
        "embed": {"tok": L.ParamSpec((cfg.padded_vocab, cfg.d_model),
                                     ("vocab", "d_model"), scale=0.02,
                                     cast=True)},
        "enc_blocks": L.stack_specs(_enc_layer_specs(cfg), cfg.encoder_layers),
        "dec_blocks": L.stack_specs(_dec_layer_specs(cfg), cfg.num_layers),
        "enc_final_norm": L.rmsnorm_spec(cfg.d_model),
        "final_norm": L.rmsnorm_spec(cfg.d_model),
    }
    if not cfg.tie_embeddings:
        specs["lm_head"] = L.ParamSpec((cfg.d_model, cfg.padded_vocab),
                                       ("d_model", "vocab"), cast=True)
    return specs


class EncDecLM:
    """Same external interface as ``repro.models.lm.LM``."""

    def __init__(self, cfg: ModelConfig, policy: ShardingPolicy, mesh,
                 compute_dtype=jnp.bfloat16, param_dtype=jnp.float32,
                 remat: bool = True, use_kernels: bool = False):
        assert cfg.encoder_layers > 0
        self.cfg = cfg
        self.policy = policy.for_mesh(mesh) if mesh is not None else policy
        self.mesh = mesh
        self.compute_dtype = compute_dtype
        self.param_dtype = param_dtype
        self.remat = remat
        self._specs = encdec_param_specs(cfg)

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

    # ---------------- encoder ----------------
    def encode(self, params, frames) -> jax.Array:
        cfg = self.cfg
        x = frames.astype(self.compute_dtype)
        x = constrain(x, self.policy, "batch", "frames", "act_d")
        B, F, _ = x.shape
        pos = jnp.broadcast_to(jnp.arange(F, dtype=jnp.int32), (B, F))

        def block(x, lp):
            h = L.rmsnorm(lp["pre_attn_norm"], x, cfg.norm_eps)
            q, k, v = L._qkv(lp["attn"], cfg, h, pos, self.policy)
            o = L.self_attention(q, k, v, causal=False)
            o = o.reshape(B, F, cfg.num_heads * cfg.head_dim)
            x = x + o @ lp["attn"]["wo"].astype(x.dtype)
            h = L.rmsnorm(lp["pre_mlp_norm"], x, cfg.norm_eps)
            x = x + L.mlp(lp["mlp"], h, self.policy)
            return x, None

        body = jax.checkpoint(block, prevent_cse=False) if self.remat else block
        x, _ = jax.lax.scan(body, x, params["enc_blocks"])
        return L.rmsnorm(params["enc_final_norm"], x, cfg.norm_eps)

    # ---------------- decoder ----------------
    def _dec_block(self, lp, x, enc_kv, pos, causal=True):
        """x (B,S,d); enc_kv = (k, v) (B,F,KV,hd)."""
        cfg = self.cfg
        B, S, _ = x.shape
        h = L.rmsnorm(lp["pre_self_norm"], x, cfg.norm_eps)
        q, k, v = L._qkv(lp["self_attn"], cfg, h, pos, self.policy)
        o = L.self_attention(q, k, v, causal=causal)
        x = x + o.reshape(B, S, -1) @ lp["self_attn"]["wo"].astype(x.dtype)

        h = L.rmsnorm(lp["pre_cross_norm"], x, cfg.norm_eps)
        q = (h @ lp["cross_attn"]["wq"].astype(x.dtype)
             ).reshape(B, S, cfg.num_heads, cfg.head_dim)
        ek, ev = enc_kv
        o = L.cross_attention(q, ek, ev)
        x = x + o.reshape(B, S, -1) @ lp["cross_attn"]["wo"].astype(x.dtype)

        h = L.rmsnorm(lp["pre_mlp_norm"], x, cfg.norm_eps)
        return x + L.mlp(lp["mlp"], h, self.policy), (k, v)

    def _cross_kv(self, lp, enc_out):
        B, F, _ = enc_out.shape
        cfg = self.cfg
        dt = enc_out.dtype
        ek = (enc_out @ lp["cross_attn"]["wk"].astype(dt)
              ).reshape(B, F, cfg.num_kv_heads, cfg.head_dim)
        ev = (enc_out @ lp["cross_attn"]["wv"].astype(dt)
              ).reshape(B, F, cfg.num_kv_heads, cfg.head_dim)
        return ek, ev

    def forward(self, params, batch) -> jax.Array:
        cfg = self.cfg
        enc_out = self.encode(params, batch["frames"])
        tokens = batch["tokens"]
        B, S = tokens.shape
        x = jnp.take(params["embed"]["tok"].astype(self.compute_dtype),
                     tokens, axis=0)
        x = constrain(x, self.policy, "batch", "seq", "act_d")
        pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))

        def block(x, lp):
            x, _ = self._dec_block(lp, x, self._cross_kv(lp, enc_out), pos)
            return x, None

        body = jax.checkpoint(block, prevent_cse=False) if self.remat else block
        x, _ = jax.lax.scan(body, x, params["dec_blocks"])
        x = L.rmsnorm(params["final_norm"], x, cfg.norm_eps)
        return self._head(params, x)

    def _head(self, params, x):
        if self.cfg.tie_embeddings:
            w = params["embed"]["tok"].astype(x.dtype).T
        else:
            w = params["lm_head"].astype(x.dtype)
        logits = L.mask_padded_vocab(x @ w, self.cfg)
        return constrain(logits, self.policy, "batch", "logit_seq", "vocab")

    def loss(self, params, batch) -> Tuple[jax.Array, Dict[str, jax.Array]]:
        logits = self.forward(params, batch)
        tokens = batch["tokens"]
        targets = jnp.roll(tokens, -1, axis=1)
        mask = batch.get("loss_mask")
        if mask is None:
            mask = jnp.ones(tokens.shape, jnp.float32)
        mask = mask.astype(jnp.float32).at[:, -1].set(0.0)
        loss, ntok = L.softmax_xent_sharded(logits, targets, mask)
        return loss, {"loss": loss, "aux_loss": jnp.zeros((), jnp.float32),
                      "ntokens": ntok}

    # ---------------- serving ----------------
    def _cache_struct(self, batch: int, max_seq: int, abstract: bool):
        cfg = self.cfg
        Ld = cfg.num_layers
        KV, hd, F = cfg.num_kv_heads, cfg.head_dim, cfg.num_audio_frames
        mk = (lambda s: jax.ShapeDtypeStruct(s, self.compute_dtype)) \
            if abstract else (lambda s: jnp.zeros(s, self.compute_dtype))
        return {
            "self_k": mk((Ld, batch, max_seq, KV, hd)),
            "self_v": mk((Ld, batch, max_seq, KV, hd)),
            "cross_k": mk((Ld, batch, F, KV, hd)),
            "cross_v": mk((Ld, batch, F, KV, hd)),
        }

    def init_cache(self, batch: int, max_seq: int):
        return self._cache_struct(batch, max_seq, abstract=False)

    def cache_abstract(self, batch: int, max_seq: int):
        return self._cache_struct(batch, max_seq, abstract=True)

    def cache_kinds(self) -> PyTree:
        """Every cache leaf holds keys and values (see ``LM.cache_kinds``)."""
        return {k: "kv" for k in ("self_k", "self_v", "cross_k", "cross_v")}

    def cache_axes(self) -> PyTree:
        ax = ("layers", "batch", "cache_seq", "kv_heads", None)
        fx = ("layers", "batch", "frames", "kv_heads", None)
        return {"self_k": ax, "self_v": ax, "cross_k": fx, "cross_v": fx}

    def cache_shardings(self, batch=None, max_seq=None):
        from repro.models.lm import _cache_policy
        from repro.sharding.policy import fit_shardings_tree
        policy = _cache_policy(self.policy, self.mesh, batch)
        sh = jax.tree.map(
            lambda a: policy.sharding(self.mesh, *a), self.cache_axes(),
            is_leaf=lambda x: isinstance(x, tuple))
        if batch is not None and max_seq is not None:
            sh = fit_shardings_tree(sh, self.cache_abstract(batch, max_seq),
                                    self.mesh)
        return sh

    def prefill(self, params, batch) -> Tuple[jax.Array, PyTree]:
        """Encode frames + run the decoder prompt, returning last-token
        logits and a populated cache (self cache length == prompt length)."""
        cfg = self.cfg
        enc_out = self.encode(params, batch["frames"])
        tokens = batch["tokens"]
        B, S = tokens.shape
        x = jnp.take(params["embed"]["tok"].astype(self.compute_dtype),
                     tokens, axis=0)
        pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))

        def block(x, lp):
            ck, cv = self._cross_kv(lp, enc_out)
            x, (sk, sv) = self._dec_block(lp, x, (ck, cv), pos)
            return x, {"self_k": sk, "self_v": sv,
                       "cross_k": ck, "cross_v": cv}

        x, cache = jax.lax.scan(block, x, params["dec_blocks"])
        x = L.rmsnorm(params["final_norm"], x[:, -1:, :], cfg.norm_eps)
        return self._head(params, x)[:, 0, :], cache

    def decode_step(self, params, cache, tokens, pos
                    ) -> Tuple[jax.Array, PyTree]:
        cfg = self.cfg
        B = tokens.shape[0]
        H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
        S_c = cache["self_k"].shape[2]
        x = jnp.take(params["embed"]["tok"].astype(self.compute_dtype),
                     tokens, axis=0)
        posv = jnp.full((B, 1), pos, jnp.int32)

        def block(x, xs):
            lp, lc = xs
            h = L.rmsnorm(lp["pre_self_norm"], x, cfg.norm_eps)
            q, k_new, v_new = L._qkv(lp["self_attn"], cfg, h[:, None, :],
                                     posv, self.policy)
            k = jax.lax.dynamic_update_slice_in_dim(lc["self_k"], k_new, pos, 1)
            v = jax.lax.dynamic_update_slice_in_dim(lc["self_v"], v_new, pos, 1)
            qg = q.reshape(B, 1, KV, H // KV, hd)
            sc = jnp.einsum("bqgrd,bkgd->bgrqk", qg, k) / np.sqrt(hd)
            sc = jnp.where((jnp.arange(S_c) <= pos)[None, None, None, None, :],
                           sc.astype(jnp.float32), -1e30)
            pr = jax.nn.softmax(sc, axis=-1).astype(x.dtype)
            o = jnp.einsum("bgrqk,bkgd->bqgrd", pr, v).reshape(B, H * hd)
            x = x + o @ lp["self_attn"]["wo"].astype(x.dtype)

            h = L.rmsnorm(lp["pre_cross_norm"], x, cfg.norm_eps)
            q = (h @ lp["cross_attn"]["wq"].astype(x.dtype)
                 ).reshape(B, 1, H, hd)
            o = L.cross_attention(q, lc["cross_k"], lc["cross_v"])
            x = x + o.reshape(B, H * hd) @ lp["cross_attn"]["wo"].astype(x.dtype)

            h = L.rmsnorm(lp["pre_mlp_norm"], x, cfg.norm_eps)
            x = x + L.mlp(lp["mlp"], h, self.policy)
            return x, {"self_k": k, "self_v": v,
                       "cross_k": lc["cross_k"], "cross_v": lc["cross_v"]}

        x, new_cache = jax.lax.scan(block, x, (params["dec_blocks"], cache))
        x = L.rmsnorm(params["final_norm"], x, cfg.norm_eps)
        if self.cfg.tie_embeddings:
            w = params["embed"]["tok"].astype(x.dtype).T
        else:
            w = params["lm_head"].astype(x.dtype)
        return L.mask_padded_vocab(x @ w, self.cfg), new_cache


def build_model(cfg: ModelConfig, policy: ShardingPolicy, mesh, **kw):
    """Factory: pick LM or EncDecLM from the config."""
    from repro.models.lm import LM
    if cfg.encoder_layers > 0:
        return EncDecLM(cfg, policy, mesh, **kw)
    return LM(cfg, policy, mesh, **kw)
