"""A replica of a hybrid model decoding long generations, snapshotted
while it decodes: short prompts, thousands of tokens out.

Set-up builds a ``DecodeServer`` as the serving launcher does, holding
the weights in the dtype the model is published in (the configuration's
``torch_dtype``), with a cache of the traffic's ``max_seq`` rows: a key
and value row per position for the attention layers beside the
recurrent state of the Mamba layers.  It loads the seed's weights in
that dtype (``weights``), prefills a batch of prompts and decodes one
chunk, so every program is compiled.  The window, the drain and the operator's
snapshots are ``serve_snapshot``'s: greedy decoding through
``decode_until`` for ``--seconds``, an asynchronous snapshot every
``snapshot_every_s`` seconds of the window.  A step that would write a
key/value row at or past ``max_seq`` raises (``SequenceFull``): the
write is never clamped, so a traffic mix that outruns its cache fails
the run instead of serving wrong tokens.

The check is ``serve_snapshot``'s: up to ``check_tokens`` served tokens
of every request (at least ``min_check_tokens``) judged against the
plain reference's logits, on the same weights in the held dtype, and
the newest snapshot restored cold into a fresh server and compared with
the live state it was taken from, bit for bit.
"""
from __future__ import annotations

import functools
import gc

import numpy as np

from bench import judge
from bench import traffic as T
from bench import weights as W
from bench.harness import Check, load_module

KIND = "serve"
_snap = load_module("drivers", "serve_snapshot")
drain = _snap.drain


def _held(cell):
    import jax.numpy as jnp
    return jnp.dtype(cell.program_config().param_dtype)


def weights(model, cell):
    """The seed's weights (``bench/weights.py``) in the held dtype, the
    tied embedding divided by the model's embedding multiplier, so the
    residual stream starts at the 0.02 scale it has in every other cell.
    Drawn at 0.02 itself, the multiplied embedding outweighs the layers'
    branches and the tied head favours each token's own row, the more so
    the wider the model (its self-score grows with the square root of
    d_model): greedy decoding falls into repeating one token (93% of the
    steps at the smoke sizes), where every gap reads 0, the float8
    control's too, and the check could tell no fault."""
    params = W.make(model.init_abstract(), cell.seed, dtype=_held(cell))
    m = cell.program_config().embedding_multiplier
    emb = params["embed"]["tok"]
    params["embed"]["tok"] = (emb.astype("float32") / m).astype(emb.dtype)
    return params


def _server(cell, model=None):
    import jax.numpy as jnp
    from repro.api import CheckpointOptions
    from repro.launch.mesh import make_host_mesh
    from repro.runtime.server import DecodeServer
    from repro.sharding import get_policy
    t = cell.traffic
    return DecodeServer(
        cell.program_config(), get_policy(t["policy"]),
        make_host_mesh(data=cell.chips, model=1),
        str(cell.run_dir / "serve"), max_seq=t["max_seq"],
        compute_dtype=getattr(jnp, t["compute_dtype"]),
        param_dtype=_held(cell),
        options=CheckpointOptions(mode=t["snapshot_mode"], keep=t["keep"]),
        model=model)


def setup(cell):
    import jax
    t = cell.traffic
    if t["prompt_len"] + t["chunk"] >= t["max_seq"]:
        raise ValueError(f"prompts of {t['prompt_len']} and a chunk of "
                         f"{t['chunk']} do not fit max_seq {t['max_seq']}")
    with cell.span("setup.build"):
        srv = _server(cell)
        srv.load(weights(srv.model, cell))
    digest = jax.jit(W.digest)
    prompts = T.TokenRows(cell.seed, t["batch"], t["prompt_len"],
                          cell.program_config().vocab_size).peek(0)
    with cell.span("setup.prefill"):
        srv.start(prompts)
    with cell.span("setup.first_tokens"):
        srv.decode_until(srv.pos + t["chunk"])
        jax.block_until_ready(digest({"params": srv.params,
                                      "cache": srv.cache}))
    return {"server": srv, "digest": digest, "digests": {}, "snaps": []}


def window(state, cell):
    """``serve_snapshot``'s window; its work also gives the positions it
    decoded from and to, for the readers that count per position."""
    first = state["server"].pos
    win = _snap.window(state, cell)
    win.work["positions"] = [first, state["server"].pos]
    return win


def check(state, cell):
    import jax
    t = cell.traffic
    srv = state.pop("server")
    tag = srv.session.latest_step()
    live = jax.device_get(state["digests"].get(tag))
    model = srv.model
    served = np.asarray(srv.tokens)
    del srv
    gc.collect()

    mismatched = len(jax.tree.leaves(live or {})) or 1
    if live is not None:
        cold = _server(cell, model=model)
        cold.restore(step=tag)
        got = jax.device_get(jax.jit(W.digest)(
            {"params": cold.params, "cache": cold.cache}))
        del cold
        gc.collect()
        mismatched = sum(int((a != b).any()) for a, b in
                         zip(jax.tree.leaves(live), jax.tree.leaves(got)))

    first = t["prompt_len"]
    n = min(t["check_tokens"], served.shape[1] - first)
    short = max(0, t["min_check_tokens"] - n)
    params = weights(model, cell)
    # the control (calibration only) judges the float8 model's choices
    gaps_of = (judge.control_gaps if t.get("variant") == "control"
               else judge.served_gaps)
    gaps = jax.jit(functools.partial(gaps_of, cell.reference()),
                   static_argnums=(2, 3))(
        params, served[:, :first + n], first, _snap._Frozen(cell.config))
    return [Check("served_logit_gap", float(np.max(gaps)), None),
            Check("served_tokens_short", float(short), 0.0),
            Check("image_leaves_differ", float(mismatched), 0.0)]
