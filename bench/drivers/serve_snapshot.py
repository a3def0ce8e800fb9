"""A serving replica snapshotted while it decodes.

Set-up builds a ``DecodeServer`` as the serving launcher does, loads
the seed's weights (float32, as the server holds them), prefills a batch
of prompts and decodes a few tokens so every program is compiled.  A
snapshot compiles nothing (the capture copies the live arrays to the
host), so set-up takes none; the benchmark's own digest of the state,
taken as each snapshot freezes it, is compiled in set-up.  The window
then decodes greedily through ``decode_until`` for ``--seconds``, and an
operator's timer takes an asynchronous snapshot every
``snapshot_every_s`` seconds of the window: how often does not depend on
how fast the replica decodes.

``decode_tokens_s`` is every token the replica produced in the window
over the window, snapshot stalls and writer contention in it.

The check judges up to ``check_tokens`` served tokens of every request
(at least ``min_check_tokens``) against the plain reference's logits,
and compares the newest committed snapshot with the live state it was
taken from, bit for bit.
"""
from __future__ import annotations

import functools
import gc
import time

import numpy as np

from bench import judge
from bench import traffic as T
from bench import weights as W
from bench.harness import Check, Window

KIND = "serve"


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
        str(cell.run_dir / "serve"), max_seq=t["prompt_len"] + 1,
        compute_dtype=getattr(jnp, t["compute_dtype"]),
        options=CheckpointOptions(mode=t["snapshot_mode"], keep=t["keep"]),
        model=model)


def setup(cell):
    import jax
    t = cell.traffic
    with cell.span("setup.build"):
        srv = _server(cell)
        srv.load(W.make(srv.model.init_abstract(), cell.seed))
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


def _snapshot(state) -> None:
    """An operator's snapshot of the replica at its position: the
    benchmark reads the live state the image is taken from (its digest,
    as it is frozen), then the server checkpoints as it does for any
    caller."""
    srv = state["server"]
    tag = srv.pos
    state["digests"][tag] = state["digest"]({"params": srv.params,
                                             "cache": srv.cache})
    t0 = time.perf_counter()
    srv.checkpoint(tag)
    state["snaps"].append((tag, time.perf_counter() - t0))


def window(state, cell) -> Window:
    srv = state["server"]
    t = cell.traffic
    p0 = srv.pos
    every, chunk = t["snapshot_every_s"], t["chunk"]
    t0 = time.perf_counter()
    deadline, due = t0 + cell.seconds, t0 + every
    while True:
        srv.decode_until(srv.pos + chunk)
        t1 = time.perf_counter()
        if t1 >= deadline:
            break
        if t1 >= due:
            _snapshot(state)
            due += every
    steps = srv.pos - p0
    tokens = steps * t["batch"]
    return Window(metrics={"decode_tokens_s": tokens / (t1 - t0)},
                  units={"decode_tokens_s": "tokens/s"},
                  attempted=tokens, failed=0, t0=t0, t1=t1,
                  work={"decode_steps": steps, "tokens": tokens,
                        # (position, seconds decoding was blocked)
                        "snapshot_blocked_s": list(state["snaps"])})


def drain(state, cell) -> None:
    state["server"].session.wait_pending()


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
    params = W.make(model.init_abstract(), cell.seed)
    # the control (calibration only) judges the float8 model's choices
    gaps_of = (judge.control_gaps if t.get("variant") == "control"
               else judge.served_gaps)
    gaps = jax.jit(functools.partial(gaps_of, cell.reference()),
                   static_argnums=(2, 3))(
        params, served[:, :first + n], first, _Frozen(cell.config))
    return [Check("served_logit_gap", float(np.max(gaps)), None),
            Check("served_tokens_short", float(short), 0.0),
            Check("image_leaves_differ", float(mismatched), 0.0)]


class _Frozen(dict):
    """A configuration dict that jit can take as a static argument."""

    def __hash__(self):
        return hash(tuple(sorted((k, repr(v)) for k, v in self.items())))
