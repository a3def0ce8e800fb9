"""Training runtime with transparent unified checkpointing.

The loop contains no checkpoint logic for its *state* — the SnapshotEngine
is attached to a state provider and captures params/optimizer/RNG (device)
plus data-cursor/metrics (host) through plugins.  Periodic and just-in-time
policies both drive the same engine.  ``run_with_restarts`` demonstrates
the full failure story: crash (SimulatedFailure or real exception) →
re-construct a fresh Trainer → engine.restore → continue — including onto a
*different mesh* (elastic restart).
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.api import CheckpointOptions, CheckpointSession
from repro.core import SnapshotEngine
from repro.data import TokenPipeline
from repro.models.config import ModelConfig
from repro.models.encdec import build_model
from repro.optim import AdamW
from repro.obs import trace as obs_trace
from repro.optim.schedule import warmup_cosine
from repro.runtime.fault import JITCheckpointPolicy, StragglerMonitor
from repro.sharding.policy import ShardingPolicy

PyTree = Any


class SimulatedFailure(RuntimeError):
    pass


@dataclasses.dataclass
class TrainConfig:
    batch_size: int = 4
    seq_len: int = 64
    lr: float = 3e-4
    warmup_steps: int = 20
    total_steps: int = 200
    ckpt_every: int = 0             # 0 = no periodic checkpoints
    ckpt: Optional[CheckpointOptions] = None   # how snapshots are taken
    ckpt_mode: str = "sync"         # deprecated: use ckpt=CheckpointOptions
    incremental: bool = False       # deprecated: use ckpt=CheckpointOptions
    seed: int = 0
    compute_dtype: Any = jnp.bfloat16
    remat: bool = True

    def checkpoint_options(self) -> CheckpointOptions:
        """Resolve the effective options (explicit `ckpt` wins over the
        deprecated per-field knobs)."""
        if self.ckpt is not None:
            return self.ckpt
        return CheckpointOptions(mode=self.ckpt_mode,
                                 incremental=self.incremental)


class Trainer:
    def __init__(self, cfg: ModelConfig, tcfg: TrainConfig, mesh,
                 policy: ShardingPolicy, run_dir: str,
                 engine: Optional[SnapshotEngine] = None,
                 replicator=None,
                 session: Optional[CheckpointSession] = None):
        self.cfg = cfg
        self.tcfg = tcfg
        self.mesh = mesh
        self.model = build_model(cfg, policy, mesh,
                                 compute_dtype=tcfg.compute_dtype,
                                 remat=tcfg.remat)
        self.opt = AdamW(lr=warmup_cosine(tcfg.lr, tcfg.warmup_steps,
                                          tcfg.total_steps))
        self.pipeline = TokenPipeline(cfg, tcfg.batch_size, tcfg.seq_len,
                                      seed=tcfg.seed)
        self.params = None
        self.opt_state = None
        self.step = 0
        self.metrics_history: Dict[str, list] = {"loss": []}
        self.straggler = StragglerMonitor()

        if session is None:
            if engine is not None:       # migration aid: wrap a bare engine
                session = CheckpointSession.from_engine(engine)
            else:
                opts = tcfg.checkpoint_options()
                if (opts.restore_mode == "lazy"
                        and opts.critical_states is None):
                    # resume-before-read default: the first step's forward
                    # pass touches params; optimizer slots are cold and
                    # stream in behind the resumed job
                    opts = opts.replace(
                        critical_states=("train_state/params",))
                session = CheckpointSession(
                    run_dir, opts, mesh=mesh,
                    replicator=replicator)
        self.session = session
        # lazy restore: the optimizer template whose leaves are still
        # streaming; joined right before the first step runs
        self._pending_opt_template = None
        self.engine = session.engine     # back-compat alias
        # transparent wiring: live state via provider, host bits via plugins
        self.session.attach(lambda: {"train_state": {
            "params": self.params, "opt": self.opt_state}})
        self.session.register_host_state(
            "data_cursor", lambda: self.pipeline.state(),
            lambda st: self.pipeline.restore_state(st))
        self.session.register_host_state(
            "trainer", lambda: {"step": self.step,
                                "loss_hist": self.metrics_history["loss"][-50:]},
            self._restore_trainer_state)
        self.jit_ckpt = JITCheckpointPolicy(self.session)

        self._step_fn = jax.jit(
            self._train_step,
            donate_argnums=(0, 1),
            in_shardings=(self.model.param_shardings(),
                          self._opt_shardings(), None),
        ) if mesh is not None and np.prod(mesh.devices.shape) > 1 else \
            jax.jit(self._train_step, donate_argnums=(0, 1))

    def _restore_trainer_state(self, st):
        self.step = st["step"]
        self.metrics_history["loss"] = list(st["loss_hist"])

    def _opt_shardings(self):
        from repro.optim.adamw import OptState
        ps = self.model.param_shardings()
        from jax.sharding import NamedSharding, PartitionSpec
        scalar = NamedSharding(self.mesh, PartitionSpec())
        return OptState(step=scalar, m=ps, v=ps)

    # ------------------------------------------------------------- steps
    def _train_step(self, params, opt_state, batch):
        def loss_fn(p):
            return self.model.loss(p, batch)
        (loss, metrics), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params)
        params, opt_state, om = self.opt.update(grads, opt_state, params)
        return params, opt_state, {**metrics, **om}

    def initialize(self) -> None:
        """Fresh params and optimizer state, created where the step
        expects them: on a mesh each leaf is born in its own layout, so
        no device ever holds the whole model."""
        self.params = jax.jit(
            self.model.init, out_shardings=self.model.param_shardings())(
                jax.random.key(self.tcfg.seed))
        self.opt_state = jax.jit(
            self.opt.init, out_shardings=self._opt_shardings())(self.params)
        self.step = 0

    def restore(self, step: Optional[int] = None, mesh=None) -> int:
        """Unified restore (engine pushes host state back via plugins).

        In lazy mode (``CheckpointOptions(restore_mode="lazy")``) this
        returns as soon as the critical set — by default the parameters —
        is placed; the optimizer slots keep streaming in the background
        and are joined right before the first step executes
        (resume-before-read)."""
        if self.params is None:
            # typed restore needs only the tree structure: an abstract
            # template allocates nothing on the device
            params = self.model.init_abstract()
            template = {"params": params,
                        "opt": self.opt.init_abstract(params)}
        else:
            template = {"params": self.params, "opt": self.opt_state}
        shardings = None
        if self.mesh is not None:
            shardings = {"params": self.model.param_shardings(),
                         "opt": self._opt_shardings()}
        if self.session.options.restore_mode == "lazy":
            restored = self.session.restore(
                step=step, mesh=mesh or self.mesh,
                shardings={"train_state": shardings}
                if shardings is not None else None,
                wait="critical")
            engine = self.session.engine
            raw = restored.get("train_state", {})
            try:
                self.params = engine.retree(template["params"],
                                            raw.get("params", {}))
            except (KeyError, RuntimeError):
                # a custom critical_states spec that does not cover the
                # whole params subtree: the leaves are still streaming
                # (or partially landed) — join and retree from the
                # complete tree instead of crashing
                raw = self.session.restore_barrier()["train_state"]
                self.params = engine.retree(template["params"],
                                            raw["params"])
            if self.session.lazy_pending:
                self._pending_opt_template = template["opt"]
            else:                       # stream finished (or joined above)
                self.opt_state = engine.retree(template["opt"], raw["opt"])
            return self.step
        restored = self.session.restore_into(
            template, state="train_state", step=step,
            mesh=mesh or self.mesh, shardings=shardings)
        self.params = restored["params"]
        self.opt_state = restored["opt"]
        return self.step

    def _finish_lazy_restore(self) -> None:
        """Join the background stream and adopt the cold optimizer slots
        — called on first touch (right before the first step, or before a
        checkpoint-on-signal captures the live roots)."""
        if self._pending_opt_template is None:
            return
        template, self._pending_opt_template = \
            self._pending_opt_template, None
        full = self.session.restore_barrier()
        self.opt_state = self.session.engine.retree(
            template, full["train_state"]["opt"])

    # ------------------------------------------------------------- loop
    def run_until(self, target_step: int,
                  preempt: Optional[Callable[[], bool]] = None,
                  fail_at: Optional[int] = None,
                  straggle_at: Optional[int] = None) -> Dict[str, Any]:
        """Run to `target_step`; resumable and preemptible.

        `preempt` is polled between steps (the SIGTERM-trap analogue): when
        it fires the trainer checkpoints-on-signal — ``session.frozen``
        dump at the current step — and returns with ``preempted=True``
        instead of raising, so an orchestrator can release the devices and
        reschedule the job.  A failed *async* snapshot write aborts the run
        promptly with :class:`SnapshotWriteFailed` rather than surfacing at
        the next explicit dump — the job must not keep running on the
        assumption that its recent checkpoints exist.
        """
        from repro.api.session import SnapshotWriteFailed
        if self.params is None:
            self.initialize()
        t_loop = time.perf_counter()
        executed = 0
        preempted = False
        ckpt_path = None
        while self.step < target_step:
            if self.session.write_error is not None:
                raise SnapshotWriteFailed(
                    f"async snapshot write failed at step {self.step}: "
                    f"{self.session.write_error}")
            handle = self.session.concurrent_capture
            if handle is not None and handle.speculation_done:
                # soft-freeze capture finished speculating in the
                # background: take the short validate pause now, between
                # steps, instead of letting it collide with a later dump
                self.session.checkpoint_finalize()
            if preempt is not None and preempt():
                # a dump captures the live roots: the cold optimizer
                # slots must have landed before the freeze
                self._finish_lazy_restore()
                # an in-flight soft-freeze capture must settle before the
                # signal dump (its validate pause re-reads the live roots)
                self.session.checkpoint_finalize()
                if (self.session.last_commit_step == self.step
                        and self.session.latest_step() == self.step):
                    # THIS incarnation committed an image of this exact
                    # step (periodic dump landed right before the
                    # signal): yield it instead of re-dumping the same
                    # state.  A same-numbered leftover from an earlier
                    # incarnation never matches last_commit_step.
                    from repro.core.snapshot_io import snapshot_dir
                    ckpt_path = snapshot_dir(self.session.run_dir,
                                             self.step)
                else:
                    with obs_trace.context(trigger="signal"), \
                            self.session.frozen(self.step) as snap:
                        pass                           # dump-and-yield
                    ckpt_path = snap.path
                preempted = True
                break
            if fail_at is not None and self.step == fail_at:
                raise SimulatedFailure(f"injected failure at {self.step}")
            with obs_trace.span("train.step", step=self.step):
                batch_np = self.pipeline.next()
                batch = {k: jnp.asarray(v) for k, v in batch_np.items()}
                # first-touch join: batch prep (and everything since
                # restore returned) overlapped the background
                # optimizer-slot stream
                self._finish_lazy_restore()
                t0 = time.perf_counter()
                if straggle_at is not None and self.step == straggle_at:
                    time.sleep(0.25)                   # injected straggler
                with jax.sharding.set_mesh(self.mesh):
                    self.params, self.opt_state, metrics = self._step_fn(
                        self.params, self.opt_state, batch)
                # after a restore this also waits for the placed state
                # to land on the device
                with obs_trace.span("train.sync"):
                    loss = float(metrics["loss"])
            self.metrics_history["loss"].append(loss)
            dt = time.perf_counter() - t0
            self.step += 1
            executed += 1
            if self.straggler.record(dt):
                with obs_trace.context(trigger="straggler"):
                    self.jit_ckpt.on_signal(self.step)  # just-in-time ckpt
            if (self.tcfg.ckpt_every
                    and self.step % self.tcfg.ckpt_every == 0):
                with obs_trace.context(trigger="periodic"):
                    if self.session.options.capture == "concurrent":
                        # soft-freeze: brief pin pause, then the loop
                        # keeps stepping while shards are speculated in
                        # background; the handle is finalized by the poll
                        # above (or the settle below if the run ends first)
                        self.session.checkpoint_begin(self.step)
                    else:
                        self.session.checkpoint(self.step)
        # never leave a capture half-done across run_until boundaries
        self.session.checkpoint_finalize()
        return {"steps": executed, "step": self.step,
                "preempted": preempted, "ckpt_path": ckpt_path,
                "loss": (self.metrics_history["loss"][-1]
                         if self.metrics_history["loss"] else None),
                "wall_s": time.perf_counter() - t_loop}

    def run(self, num_steps: int, fail_at: Optional[int] = None,
            straggle_at: Optional[int] = None) -> Dict[str, Any]:
        if self.params is None:
            self.initialize()
        t_loop = time.perf_counter()
        self.run_until(self.step + num_steps, fail_at=fail_at,
                       straggle_at=straggle_at)
        self.session.wait_pending()
        return {"steps": self.step,
                "loss": self.metrics_history["loss"][-1],
                "wall_s": time.perf_counter() - t_loop}


def run_with_restarts(make_trainer, total_steps: int,
                      failures: Dict[int, str]) -> Dict[str, Any]:
    """Drive training to `total_steps`, surviving injected failures.

    failures: {step: kind} — trainer is rebuilt from scratch and restored
    from the newest valid snapshot after each crash (node-replacement
    semantics).
    """
    restarts = 0
    trainer = make_trainer()
    trainer.initialize()
    pending = dict(failures)
    while trainer.step < total_steps:
        fail_at = min((s for s in pending if s >= trainer.step),
                      default=None)
        try:
            trainer.run(total_steps - trainer.step, fail_at=fail_at)
        except SimulatedFailure:
            # the crashed trainer's async write would otherwise race its
            # replacement's restore and dumps in this same process
            trainer.session.wait_pending()
            pending.pop(fail_at, None)
            restarts += 1
            trainer = make_trainer()                   # replacement node
            trainer.restore()                          # newest valid image
    return {"steps": trainer.step, "restarts": restarts,
            "loss_history": trainer.metrics_history["loss"],
            "trainer": trainer}
