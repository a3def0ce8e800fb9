"""Batched decode server with transparent serving-state snapshots.

Serving state (KV/SSM caches + generated tokens + positions) is device
state like any other — the engine checkpoints a half-finished generation
and a fresh server resumes it token-exact.  This is the inference-side
story of the paper (Modal/MemVerge deployments snapshot serving processes
for fast cold-start).
"""
from __future__ import annotations

import time
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from repro.api import CheckpointOptions, CheckpointSession
from repro.models.config import ModelConfig
from repro.models.encdec import build_model
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace
from repro.sharding.policy import ShardingPolicy


# Decode steps the loop leaves running on the device behind the one it
# dispatches.  Each step in flight pins a whole cache version, since the
# cache is not donated (0.84 GB at the granite cell's 16,384 rows).  Two
# queued steps give the device 7-18 ms of work in the serving cells on a
# TPU v5e, which covers the host's fetch of a token plus its next dispatch.
_LAG = 2


class SequenceFull(RuntimeError):
    """A decode step would write a key/value row at or past ``max_seq``."""


class DecodeServer:
    def __init__(self, cfg: ModelConfig, policy: ShardingPolicy, mesh,
                 run_dir: str, max_seq: int = 256,
                 compute_dtype=jnp.float32,
                 options: Optional[CheckpointOptions] = None,
                 session: Optional[CheckpointSession] = None,
                 model=None, param_dtype=jnp.float32):
        self.cfg = cfg
        # `model=` lets a fleet of replicas share one model (and one jit
        # cache) instead of recompiling per server, and its dtypes
        self.model = model if model is not None else build_model(
            cfg, policy, mesh, compute_dtype=compute_dtype,
            param_dtype=param_dtype, remat=False)
        self.max_seq = max_seq
        # full attention writes a key/value row per position into a cache
        # of max_seq rows; a windowed or recurrent layer never fills up
        self._kv_rows = max_seq if "attn" in cfg.layer_pattern else None
        self.params = None             # the held weights (param_dtype)
        self.cache = None
        self.tokens: Optional[np.ndarray] = None       # generated so far
        self.pos = 0
        if session is None:
            if (options is not None and options.restore_mode == "lazy"
                    and options.critical_states is None):
                # resume-before-read default: the decode loop touches
                # params immediately; the cache streams in behind the
                # resumed server and is joined before its first step
                options = options.replace(
                    critical_states=("serve_state/params",))
            session = CheckpointSession(run_dir, options, mesh=mesh)
        self.session = session
        self._pending_cache_template = None   # lazy: cache still streaming
        self.engine = self.session.engine              # back-compat alias
        self.session.attach(lambda: {"serve_state": {
            "params": self.params, "cache": self.cache}})
        self.session.register_host_state(
            "decode_cursor",
            lambda: {"pos": self.pos,
                     "tokens": self.tokens},
            self._restore_cursor)
        jits = getattr(self.model, "_decode_server_jit", None)
        if jits is None:
            jits = (jax.jit(self.model.prefill),
                    jax.jit(self.model.decode_step))
            self.model._decode_server_jit = jits
        self._prefill, self._decode = jits

    @property
    def params(self):
        return self._params

    @params.setter
    def params(self, value) -> None:
        # every assignment (load, each restore path, a caller) drops the
        # compute copy: it is rebuilt from the new weights at first use
        self._params = value
        self._compute = None

    def _compute_params(self):
        """The weights as the prefill and decode programs take them: the
        model's compute copy of ``params``, built once per load or
        restore.  Never captured: the image holds ``params``."""
        if self._compute is None:
            with obs_trace.span("serve.cast"):
                self._compute = self.model.compute_params(self._params)
            if self._compute is not self._params:
                obs_metrics.counter_add("serve.weights_cast")
        return self._compute

    def _restore_cursor(self, st):
        self.pos = st["pos"]
        self.tokens = st["tokens"]

    def load(self, params) -> None:
        self.params = params
        self._gauge_bytes()

    def _gauge_bytes(self) -> None:
        """Gauge what the server holds on the device: its weights
        (``serve.bytes.params``) and its cache by the kind of layer each
        leaf belongs to (``serve.bytes.kv``: keys and values;
        ``serve.bytes.state``: recurrent state and convolution tails)."""
        if obs_metrics.REGISTRY is None:
            return
        if self._params is not None:
            obs_metrics.gauge_set("serve.bytes.params", float(sum(
                x.nbytes for x in jax.tree.leaves(self._params))))
        if self.cache is not None:
            held = {"kv": 0, "state": 0}
            for kind, leaf in zip(jax.tree.leaves(self.model.cache_kinds()),
                                  jax.tree.leaves(self.cache)):
                held[kind] += leaf.nbytes
            for kind, n in held.items():
                obs_metrics.gauge_set(f"serve.bytes.{kind}", float(n))

    # ------------------------------------------------------------- serving
    def start(self, batch: Dict[str, Any]) -> None:
        """Prefill a batch of prompts; cache is padded to max_seq."""
        prompt = batch["tokens"]
        B, S = prompt.shape
        logits, cache = self._prefill(self._compute_params(),
                                      {k: jnp.asarray(v)
                                       for k, v in batch.items()})
        self.cache = self._pad_cache(cache, self.max_seq)
        self._gauge_bytes()
        nxt = np.asarray(jnp.argmax(logits, axis=-1), np.int32)
        self.tokens = np.concatenate([np.asarray(prompt, np.int32),
                                      nxt[:, None]], axis=1)
        self.pos = S

    def _pad_cache(self, cache, max_seq):
        """Pad the prefill's key/value leaves to the rows the decode cache
        has at ``max_seq`` (``model.cache_abstract``).  Leaves are told
        apart by their layer's kind (``model.cache_kinds``): recurrent
        state has a fixed size and is never padded."""
        B = jax.tree.leaves(cache)[0].shape[1]

        def pad(kind, leaf, want):
            if kind != "kv" or leaf.shape == want.shape:
                return leaf
            short = [w - n for n, w in zip(leaf.shape, want.shape)]
            if min(short) < 0:
                raise ValueError(f"a cache leaf of shape {leaf.shape} does "
                                 f"not fit max_seq={max_seq} "
                                 f"{want.shape}")
            return jnp.pad(leaf, [(0, n) for n in short])

        return jax.tree.map(pad, self.model.cache_kinds(), cache,
                            self.model.cache_abstract(B, max_seq))

    def decode_until(self, target_pos: int,
                     preempt: Optional[Callable[[], bool]] = None,
                     fail_at: Optional[int] = None,
                     straggle_at: Optional[int] = None) -> Dict[str, Any]:
        """Decode to `target_pos`; resumable and preemptible.

        Mirrors ``Trainer.run_until``: `preempt` is polled between tokens
        and triggers a checkpoint-on-signal (``session.frozen`` at the
        current position) before yielding; a failed async snapshot write
        aborts the generation promptly with :class:`SnapshotWriteFailed`.

        The loop is pipelined: each step reads the token the previous
        step's argmax left on the device, and the host fetches tokens
        behind the device, up to ``_LAG`` steps late.  ``pos`` counts the
        steps dispatched; the history catches up with it (a drain) before
        the call returns, yields a snapshot or raises, so whatever
        observes ``pos`` and ``tokens`` finds ``tokens.shape[1] == pos + 1``.
        """
        from repro.api.session import SnapshotWriteFailed
        t0 = time.perf_counter()
        executed = 0
        preempted = False
        ckpt_path = None
        pending: List[jax.Array] = []   # this call's tokens not in `tokens`
        waited = 0                      # of them, those the host waited for
        last = None                     # the token the next step reads
        try:
            while self.pos < target_pos:
                if self.session.write_error is not None:
                    raise SnapshotWriteFailed(
                        f"async snapshot write failed at pos {self.pos}: "
                        f"{self.session.write_error}")
                if preempt is not None and preempt():
                    self._drain(pending)
                    # a dump captures the live roots: the streaming cache
                    # must have landed before the freeze
                    self._finish_lazy_restore()
                    if (self.session.last_commit_step == self.pos
                            and self.session.latest_step() == self.pos):
                        # THIS incarnation committed an image at this
                        # exact position: yield it instead of re-dumping
                        from repro.core.snapshot_io import snapshot_dir
                        ckpt_path = snapshot_dir(self.session.run_dir,
                                                 self.pos)
                    else:
                        with self.session.frozen(self.pos) as snap:
                            pass                       # dump-and-yield
                        ckpt_path = snap.path
                    preempted = True
                    break
                if fail_at is not None and self.pos == fail_at:
                    from repro.runtime.trainer import SimulatedFailure
                    raise SimulatedFailure(
                        f"injected failure at pos {self.pos}")
                if straggle_at is not None and self.pos == straggle_at:
                    time.sleep(0.25)               # injected straggler
                # first-touch join of the lazily-streaming cache
                self._finish_lazy_restore()
                if self._kv_rows is not None and self.pos >= self._kv_rows:
                    raise SequenceFull(
                        f"position {self.pos} is past the {self._kv_rows} "
                        f"key/value rows of the cache (max_seq)")
                with obs_trace.span("serve.step", pos=self.pos) as step:
                    if len(pending) - waited > _LAG:
                        # the device runs steps in order: once this token
                        # is ready, so are all before it
                        waited = len(pending) - _LAG
                        with obs_trace.span("serve.sync"):
                            pending[waited - 1].block_until_ready()
                    step.set(ahead=len(pending) - waited)
                    if last is None:
                        on = self._token_sharding()
                        last = jnp.asarray(self.tokens[:, -1])
                    if on is not None:
                        last = jax.device_put(last, on)
                    logits, self.cache = self._decode(
                        self._compute_params(), self.cache, last,
                        jnp.int32(self.pos))
                    last = jnp.argmax(logits, axis=-1)
                    last.copy_to_host_async()
                    pending.append(last)
                    self.pos += 1
                    executed += 1
                    if self.pos >= target_pos:     # the drain at return
                        with obs_trace.span("serve.sync"):
                            self._drain(pending)
        finally:
            self._drain(pending)           # before an exception leaves
        return {"steps": executed, "pos": self.pos, "preempted": preempted,
                "ckpt_path": ckpt_path,
                "wall_s": time.perf_counter() - t0}

    def _drain(self, pending: List[jax.Array]) -> None:
        """Fetch the tokens still pending and append them to the history
        in one concatenation: ``tokens`` catches up with ``pos``."""
        if pending:
            got = np.stack([np.asarray(t, np.int32) for t in pending], axis=1)
            self.tokens = np.concatenate([self.tokens, got], axis=1)
            pending.clear()

    def _token_sharding(self):
        """Where each step's input token is put: replicated over the
        devices the cache is committed to.  The token staged from the host
        for a call's first step and the argmax outputs that feed the later
        ones then share one placement, and the decode program one
        signature.  None where the cache is not committed, for its argmax
        is not either and the staged token matches it as it is."""
        leaf = jax.tree.leaves(self.cache)[0]
        if not leaf.committed:
            return None
        sh = leaf.sharding
        if isinstance(sh, NamedSharding):
            return NamedSharding(sh.mesh, PartitionSpec())
        return sh if len(sh.device_set) == 1 else None

    def decode(self, n_tokens: int) -> np.ndarray:
        self.decode_until(self.pos + n_tokens)
        return self.tokens

    # ------------------------------------------------------------- ckpt
    def checkpoint(self, tag: int = 0) -> str:
        # a dump captures self.cache through the provider: the lazily
        # streaming cache must be adopted first, or the image would pair
        # restored params with the pre-restore cache
        self._finish_lazy_restore()
        return self.session.checkpoint(tag)

    def _boot_template(self, template):
        """Fill missing template subtrees with abstract skeletons.

        Cold boot: by the time this runs, ``session.restore`` has already
        replayed the ``decode_cursor`` host state, so the live batch size
        comes from the restored tokens; the model supplies abstract
        params/cache trees and ``retree`` only needs their structure.
        """
        if template["params"] is None:
            template = dict(template, params=self.model.init_abstract())
        if template["cache"] is None:
            if self.tokens is None:
                raise RuntimeError(
                    "cold restore needs the decode_cursor host state in "
                    "the image to size the cache skeleton")
            B = int(np.asarray(self.tokens).shape[0])
            template = dict(template,
                            cache=self.model.cache_abstract(B, self.max_seq))
        return template

    def restore(self, params_template=None, step: Optional[int] = None):
        """Resume a generation from its image — warm or cold.

        A warm server (started, or loaded with params) restores into its
        live trees; a cold one (fresh object, nothing loaded) derives
        abstract skeletons from the model once the snapshot's host state
        has replayed the decode cursor — no prefill re-execution, no
        hand-crafted cache skeleton.
        """
        template = {"params": self.params if self.params is not None
                    else params_template,
                    "cache": self.cache}
        engine = self.session.engine
        if self.session.options.restore_mode == "lazy":
            # resume-before-read: params place now, the KV cache streams
            # behind the server and is joined before the first decode step
            restored = self.session.restore(step=step, wait="critical")
            template = self._boot_template(template)
            raw = restored.get("serve_state", {})
            try:
                self.params = engine.retree(template["params"],
                                            raw.get("params", {}))
            except (KeyError, RuntimeError):
                # critical spec did not cover the whole params subtree:
                # join the stream and retree from the complete tree
                raw = self.session.restore_barrier()["serve_state"]
                self.params = engine.retree(template["params"],
                                            raw["params"])
            if self.session.lazy_pending:
                self._pending_cache_template = template["cache"]
            else:
                self.cache = engine.retree(template["cache"], raw["cache"])
            self._gauge_bytes()
            return self.pos
        if template["params"] is None or template["cache"] is None:
            raw = self.session.restore(step=step, wait="all")
            template = self._boot_template(template)
            serve = raw["serve_state"]
            self.params = engine.retree(template["params"], serve["params"])
            self.cache = engine.retree(template["cache"], serve["cache"])
            self._gauge_bytes()
            return self.pos
        restored = self.session.restore_into(template, state="serve_state",
                                             step=step)
        self.params = restored["params"]
        self.cache = restored["cache"]
        self._gauge_bytes()
        return self.pos

    def _finish_lazy_restore(self) -> None:
        """Join the background stream and adopt the cold KV cache."""
        if self._pending_cache_template is None:
            return
        template, self._pending_cache_template = \
            self._pending_cache_template, None
        full = self.session.restore_barrier()
        self.cache = self.session.engine.retree(
            template, full["serve_state"]["cache"])
        self._gauge_bytes()
