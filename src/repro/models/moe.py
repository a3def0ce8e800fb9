"""Expert-parallel Mixture-of-Experts FFN with explicit collectives.

Layout (production posture):
  * tokens are sharded over the DP axes ("pod","data"); activations are
    replicated over the TP/EP axis ("model");
  * expert weights are sharded over "model" on the expert dim (EP) and over
    "data" on the d_model dim (FSDP/ZeRO-3);
  * each model shard computes its local experts for all local tokens and the
    top-k mixture is completed by a single psum over "model" — the same
    collective volume as a Megatron row-parallel FFN, with no all-to-all.

The block is written with ``jax.shard_map`` so the collective schedule is
explicit and stable for the roofline analysis (GSPMD propagation through the
scatter/gather dispatch is otherwise unpredictable).

Dispatch is sort-free and matmul-free (no O(T·E·C·d) one-hot einsums that
would pollute HLO_FLOPs): an (E_local, C) index table is built by a cumsum
over the top-k assignment one-hot (T·k × E_local ints) and tokens are
gathered/scattered through it.  Tokens over per-expert capacity
C = ceil(T·k/E · capacity_factor) are dropped (standard GShard semantics).
"""
from __future__ import annotations

import functools
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.layers import ParamSpec
from repro.sharding.policy import ShardingPolicy

CAPACITY_FACTOR = 1.25


def moe_specs(cfg) -> Dict[str, ParamSpec]:
    E, d, f = cfg.moe_num_experts, cfg.d_model, cfg.moe_d_ff
    return {
        "router": ParamSpec((d, E), (None, None)),   # replicated (tiny)
        "w_gate": ParamSpec((E, d, f), ("experts", "d_model", "moe_ff")),
        "w_up": ParamSpec((E, d, f), ("experts", "d_model", "moe_ff")),
        "w_down": ParamSpec((E, f, d), ("experts", "moe_ff", "d_model")),
    }


def capacity(tokens: int, k: int, num_experts: int,
             factor: float = CAPACITY_FACTOR) -> int:
    # an expert can receive at most `tokens` assignments, so C is capped there
    return min(tokens, max(k, int(np.ceil(tokens * k / num_experts * factor))))


def _local_moe(x, router, w_gate, w_up, w_down, *, cfg, ep_axes, fsdp_axes,
               dp_axes, dropless):
    """Per-shard body.  x (T_loc, d) f32/bf16, expert weights local slices."""
    E, k = cfg.moe_num_experts, cfg.moe_top_k
    T = x.shape[0]

    # FSDP: un-shard the d_model dim of the local expert weights
    for ax in fsdp_axes:
        w_gate = jax.lax.all_gather(w_gate, ax, axis=1, tiled=True)
        w_up = jax.lax.all_gather(w_up, ax, axis=1, tiled=True)
        w_down = jax.lax.all_gather(w_down, ax, axis=2, tiled=True)
    E_loc = w_gate.shape[0]
    first_e = (jax.lax.axis_index(ep_axes[0]) * E_loc) if ep_axes else 0

    # ---- routing (computed redundantly on every model shard) ----
    logits = (x.astype(jnp.float32) @ router.astype(jnp.float32))     # (T,E)
    probs = jax.nn.softmax(logits, axis=-1)
    top_w, top_e = jax.lax.top_k(probs, k)                            # (T,k)
    top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)            # renorm

    # aux load-balance loss (Switch): E * sum_e f_e * P_e
    assign = jax.nn.one_hot(top_e[:, 0], E, dtype=jnp.float32)
    f_e = jnp.mean(assign, axis=0)
    P_e = jnp.mean(probs, axis=0)
    aux = E * jnp.sum(f_e * P_e)

    # ---- dispatch table ----
    C = T if dropless else capacity(T, k, E, cfg.moe_capacity_factor)
    flat_e = top_e.reshape(-1)                                        # (T*k,)
    flat_t = jnp.repeat(jnp.arange(T), k)
    flat_w = top_w.reshape(-1)
    local_e = flat_e - first_e
    is_local = (local_e >= 0) & (local_e < E_loc)
    onehot = (local_e[:, None] == jnp.arange(E_loc)[None, :]) & is_local[:, None]
    slot_per_e = jnp.cumsum(onehot.astype(jnp.int32), axis=0) - 1     # (T*k,E_loc)
    slot = jnp.sum(jnp.where(onehot, slot_per_e, 0), axis=1)          # (T*k,)
    keep = is_local & (slot < C)
    le_c = jnp.where(keep, local_e, 0)
    slot_c = jnp.where(keep, slot, C)          # overflow slot C = garbage

    table = jnp.zeros((E_loc, C + 1), jnp.int32).at[le_c, slot_c].set(flat_t)
    wtab = jnp.zeros((E_loc, C + 1), jnp.float32).at[le_c, slot_c].set(flat_w)
    vtab = jnp.zeros((E_loc, C + 1), jnp.bool_).at[le_c, slot_c].set(keep)
    table, wtab, vtab = table[:, :C], wtab[:, :C], vtab[:, :C]

    # ---- expert compute ----
    dt = x.dtype
    xin = x[table.reshape(-1)].reshape(E_loc, C, -1)                  # (E,C,d)
    xin = jnp.where(vtab[..., None], xin, 0).astype(dt)
    g = jnp.einsum("ecd,edf->ecf", xin, w_gate.astype(dt))
    u = jnp.einsum("ecd,edf->ecf", xin, w_up.astype(dt))
    h = jax.nn.silu(g) * u
    out = jnp.einsum("ecf,efd->ecd", h, w_down.astype(dt))
    out = out * (wtab * vtab)[..., None].astype(dt)

    # ---- combine: scatter-add back (f32), then sum expert shards ----
    y = jnp.zeros((T, x.shape[-1]), jnp.float32).at[table.reshape(-1)].add(
        out.reshape(-1, x.shape[-1]).astype(jnp.float32))
    if ep_axes:
        y = jax.lax.psum(y, ep_axes)
    y = y.astype(x.dtype)
    if dp_axes:
        aux = jax.lax.pmean(aux, dp_axes)
    return y, aux


def moe_block(params, cfg, x: jax.Array, policy: ShardingPolicy,
              mesh, dropless: bool = False) -> Tuple[jax.Array, jax.Array]:
    """x (B, S, d) -> (y (B, S, d), aux_loss scalar)."""
    from jax.sharding import PartitionSpec as P

    B, S, d = x.shape
    xt = x.reshape(B * S, d)
    if mesh is None:
        # single-device fallback (smoke tests): same math, no collectives
        y, aux = _local_moe(xt, params["router"], params["w_gate"],
                            params["w_up"], params["w_down"], cfg=cfg,
                            ep_axes=(), fsdp_axes=(), dp_axes=(),
                            dropless=dropless)
        return y.reshape(B, S, d), aux
    dp = tuple(a for a in policy.dp if a in mesh.axis_names)
    ep = tuple(a for a in policy.ep if a in mesh.axis_names)
    fsdp = tuple(a for a in policy.fsdp if a in mesh.axis_names
                 and a not in ep        # expert dim owns its axes
                 and policy.zero_stage >= 3)
    if len(ep) != 1:
        raise ValueError(f"MoE block requires single-axis EP, got {ep}")
    dp_size = int(np.prod([mesh.shape[a] for a in dp])) if dp else 1
    dp_sharded = (B * S) % max(dp_size, 1) == 0 and dp_size > 1
    tok_spec = P(dp if dp_sharded else None, None)

    body = functools.partial(
        _local_moe, cfg=cfg, ep_axes=ep, fsdp_axes=fsdp,
        dp_axes=dp if dp_sharded else (), dropless=dropless)
    y, aux = jax.shard_map(
        body, mesh=mesh, check_vma=False,
        in_specs=(tok_spec, P(None, None),
                  P(ep[0], fsdp if fsdp else None, None),
                  P(ep[0], fsdp if fsdp else None, None),
                  P(ep[0], None, fsdp if fsdp else None)),
        out_specs=(tok_spec, P()),
    )(xt, params["router"], params["w_gate"], params["w_up"],
      params["w_down"])
    return y.reshape(B, S, d), aux

