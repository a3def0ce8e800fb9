"""SnapshotEngine — unified, transparent CPU+device checkpointing.

The CRIUgpu workflow (paper Fig. 4a), adapted to the JAX runtime:

  checkpoint(step):
    init plugins("dump")
    ① PAUSE_DEVICES        lock: drain async dispatch (timeout → abort and
                           leave the job running, paper §3.1.1)
    ② CHECKPOINT_DEVICES   device→host: copy every addressable shard into
                           host memory (replica-0 dedup)
    ③ DUMP_EXT_STATE       host-side state via plugins (data cursor, RNG,
                           metrics — the CRIU memory-dump analogue)
    ④ write + commit       pack files, then MANIFEST.json atomically;
                           sync mode: before resuming (paper-faithful —
                           the app is "frozen" for dump+write);
                           async mode: resume after ②/③, write in a
                           background thread (beyond-paper, CheckFreq-style)
    exit plugins(success)

  restore(step, mesh, shardings):
    read newest valid manifest (CRC-verified, torn images skipped)
    RESTORE_EXT_STATE → UPDATE_TOPOLOGY_MAP → RESUME_DEVICES_LATE
    identical topology → 1:1 shard placement; different → elastic reshard.

Transparency contract: the training/serving code never defines checkpoint
logic.  The runtime attaches a *state provider* (a zero-arg callable
returning the live root pytrees — the "process tree"), and host-side bits
register CallbackPlugins.  Device state is captured from the arrays
themselves (avals + shardings + shard buffers), exactly as the driver owns
GPU state in CRIUgpu.
"""
from __future__ import annotations

import os
import threading
import time
import warnings
from typing import Any, Callable, Dict, List, Optional

import jax

from repro.chaos import hooks as chaos_hooks
from repro.core.dirty import DirtyTracker
from repro.obs import journal as obs_journal
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace
from repro.core.lock import LockTimeout
from repro.core.plugins import (CallbackPlugin, Hook, HookContext, Plugin,
                                PluginRegistry)
from repro.core.snapshot_io import (SnapshotStore, SnapshotWriter,
                                    pack_host_blob)
from repro.core.streams import UnsafeOpInFlight
from repro.core.topology import mesh_fingerprint

PyTree = Any
StateProvider = Callable[[], Dict[str, PyTree]]

_UNSET = object()          # sentinel: legacy kwarg not explicitly passed


class CheckpointAborted(RuntimeError):
    pass


def _count_trigger() -> None:
    """Count a dump begun under ``dump.trigger.<t>``: the ``trigger`` in
    the caller's span context (the trainer sets periodic, straggler or
    signal), ``call`` where none is set."""
    if obs_metrics.REGISTRY is not None:
        trigger = obs_trace.current_context().get("trigger", "call")
        obs_metrics.counter_add(f"dump.trigger.{trigger}")


class PendingWriteStalled(TimeoutError):
    """wait_pending(timeout_s=...) found the background writer still
    running past the deadline.  The thread is left joinable: call
    wait_pending() again (with or without a timeout) once the I/O
    recovers, or inspect ``engine.write_error`` after it dies."""

    def __init__(self, step, waited_s: float):
        self.step = step
        self.waited_s = waited_s
        super().__init__(
            f"async snapshot write for step {step} still running after "
            f"{waited_s:.1f}s — the writer thread may be wedged on "
            f"degraded I/O; it remains joinable (retry wait_pending() "
            f"or check write_error)")


class SnapshotEngine:
    """Checkpoint/restore mechanism.

    Canonical construction is ``SnapshotEngine(run_dir, options=opts)``
    where `opts` is a :class:`repro.api.CheckpointOptions`; most callers
    should go one level higher and use :class:`repro.api.CheckpointSession`.
    The historical per-knob keyword form still works but is a deprecated
    shim over the options object.
    """

    def __init__(self, run_dir: str,
                 plugins: Optional[List[Plugin]] = None,
                 mode=_UNSET,                        # "sync" | "async"
                 incremental=_UNSET,
                 compress=_UNSET,
                 keep=_UNSET,                        # 0 = keep all
                 lock_timeout_s=_UNSET,
                 replicator=None,                    # core.replication peer
                 restore_threads=_UNSET,             # parallel entry loads
                 mesh=None,
                 options=None,                       # api.CheckpointOptions
                 backend=None):                      # name | Plugin instance
        from repro.api.options import CheckpointOptions
        legacy = {k: v for k, v in dict(
            mode=mode, incremental=incremental, compress=compress,
            keep=keep, lock_timeout_s=lock_timeout_s,
            restore_threads=restore_threads).items() if v is not _UNSET}
        if legacy:
            if options is not None:
                raise TypeError(
                    "pass either options=CheckpointOptions(...) or legacy "
                    f"keyword(s) {sorted(legacy)}, not both")
            warnings.warn(
                "SnapshotEngine(mode=..., incremental=..., ...) keyword "
                "soup is deprecated; pass "
                "options=repro.api.CheckpointOptions(...) or use "
                "repro.api.CheckpointSession",
                DeprecationWarning, stacklevel=2)
            options = CheckpointOptions(**legacy)
        self.options = options if options is not None else CheckpointOptions()
        self.options.validate()

        self.run_dir = run_dir
        os.makedirs(run_dir, exist_ok=True)
        self.store = SnapshotStore(run_dir)
        self.device_plugin = self._make_backend(backend)
        self.registry = PluginRegistry([self.device_plugin]
                                       + list(plugins or []))
        self.mode = self.options.mode
        self.incremental = self.options.incremental
        self.compress = self.options.compress
        self.keep = self.options.keep
        self.replicator = replicator
        if replicator is None and self.options.replicate_to:
            policy = self.options.transfer_policy
            if policy is not None and policy.mode == "delta":
                from repro.transfer import DeltaReplicator
                self.replicator = DeltaReplicator(
                    self.options.replicate_to, workers=policy.workers)
            else:
                from repro.core.replication import DirReplicator
                self.replicator = DirReplicator(self.options.replicate_to)
        self.mesh = mesh
        if self.options.capture == "concurrent":
            from repro.api.options import OptionsError
            feats = getattr(self.device_plugin, "features", frozenset())
            if "dirty_tracking" not in feats:
                raise OptionsError(
                    f"capture='concurrent' needs a backend with the "
                    f"'dirty_tracking' feature; backend "
                    f"{getattr(self.device_plugin, 'backend_name', self.device_plugin.name)!r} "
                    f"offers {sorted(feats)} (sync-only capture)")
        self._concurrent: Optional["ConcurrentCapture"] = None
        self._provider: Optional[StateProvider] = None
        self._pending: Optional[threading.Thread] = None
        self._pending_ctx: Optional[HookContext] = None
        self._pending_err: List[BaseException] = []
        self._write_error: Optional[str] = None
        # lazy-restore stream state: at most one background materializer
        # per engine; a failed stream quarantines its step so the retry's
        # newest-valid scan falls back past it (eager semantics)
        self._lazy = None
        self._lazy_ctx = None
        self._lazy_step: Optional[int] = None
        self._last_restored: Optional[Dict[str, Any]] = None
        self._quarantined: set = set()
        self.last_stats: Dict[str, Any] = {}
        # step of the newest image committed by THIS engine instance —
        # lets callers distinguish "an image of step N exists" from "WE
        # dumped step N" (a leftover from a previous incarnation may
        # carry a different trajectory)
        self.last_commit_step: Optional[int] = None

    def _make_backend(self, backend) -> Plugin:
        from repro.core.backends import create_backend
        if backend is None:
            backend = "jax"
        if isinstance(backend, str):
            return create_backend(
                backend, lock_timeout_s=self.options.lock_timeout_s,
                restore_threads=self.options.restore_threads)
        return backend                     # pre-built DeviceBackend plugin

    # ------------------------------------------------------------ wiring
    def attach(self, provider: StateProvider) -> None:
        """Attach the live state roots (the 'process tree')."""
        self._provider = provider

    def register_host_state(self, name: str, getter: Callable[[], Any],
                            setter: Callable[[Any], None]) -> None:
        self.registry.add(CallbackPlugin(name, getter, setter))

    def add_plugin(self, plugin: Plugin) -> None:
        self.registry.add(plugin)

    # ------------------------------------------------------------ dump
    def checkpoint(self, step: int) -> str:
        """Create a unified snapshot.  Returns the snapshot directory.

        With ``options.capture == "concurrent"`` this still blocks until
        the image commits, but runs the soft-freeze protocol (pin →
        speculate → validate → patch → commit); callers that want the
        overlap use :meth:`begin_concurrent` and step between ``begin``
        and ``finalize``."""
        return self.snapshot_while_running(step)

    def snapshot_while_running(self, step: int) -> str:
        """Commit a snapshot of `step` while minimizing the pause the job
        observes — the capture primitive behind each pre-copy migration
        round (and the body of :meth:`checkpoint`, which shares it).

        With ``capture="concurrent"`` this is the soft-freeze protocol
        (the job is only paused for the pin + validate windows, and the
        bulk speculation overlaps its next steps); otherwise it degrades
        to an ordinary stop-the-world dump — correctness is identical,
        only the pause differs.  Returns the snapshot directory either
        way, so migration code can push the image without caring which
        capture path ran.
        """
        if self.options.capture == "concurrent":
            handle = self.begin_concurrent(step)
            handle.wait_speculated()
            return handle.finalize()
        return self.commit_dump(self.freeze(step))

    def freeze(self, step: int) -> HookContext:
        """Phases ①–③: quiesce devices and capture device+host state.

        On return the image exists *in host memory* and the job is frozen
        (device lock held).  Finish with :meth:`commit_dump` (write +
        manifest + resume) or :meth:`abort_dump` (resume, no image) — the
        session's ``frozen()`` context manager wraps exactly this pair.
        """
        if self._provider is None:
            raise RuntimeError("no state provider attached")
        if self._concurrent is not None:
            # settle any in-flight soft-freeze capture first: a second
            # dump must never interleave with an open stripe set
            self._concurrent.finalize()
        self.wait_pending()
        if self._lazy is not None:
            # a dump must never freeze a half-restored job: join the
            # background stream first (raises if it died — the caller's
            # state is incomplete and must not be captured as an image)
            self.restore_barrier()

        _count_trigger()
        ctx = HookContext("dump", step)
        ctx.roots = self._provider()
        self.registry.init_all("dump")
        ctx.stats["t_start"] = time.perf_counter()
        try:
            with obs_trace.span("dump.pause", step=step):
                self.registry.run(Hook.PAUSE_DEVICES, ctx)   # ① lock
            t_frozen = time.perf_counter()
            with obs_trace.span("dump.capture", step=step) as sp:
                self.registry.run(Hook.CHECKPOINT_DEVICES, ctx)  # ② dev→host
                captured = ctx.stats.get("device_bytes", 0.0)
                sp.set(bytes=captured)
            obs_metrics.counter_add("dump.captured_bytes", captured)
            with obs_trace.span("dump.ext_state", step=step):
                self.registry.run(Hook.DUMP_EXT_STATE, ctx)  # ③ host state
            ctx.stats["frozen_s"] = time.perf_counter() - t_frozen
        except LockTimeout as e:
            # abort-to-running: nothing was mutated; plugins may roll back
            self.registry.exit_all("dump", False)
            raise CheckpointAborted(str(e)) from e
        except UnsafeOpInFlight as e:
            # abort-to-running: async work could not be quiesced at the
            # capture boundary — resume rather than snapshot torn state
            self.device_plugin.lock.unlock()
            self.registry.exit_all("dump", False)
            raise CheckpointAborted(str(e)) from e
        except Exception:
            self.device_plugin.lock.unlock()
            self.registry.exit_all("dump", False)
            raise
        return ctx

    def abort_dump(self, ctx: HookContext) -> None:
        """Abandon a frozen dump: resume the job, write nothing."""
        self.device_plugin.lock.unlock()
        self.registry.exit_all("dump", False)

    def commit_dump(self, ctx: HookContext) -> str:
        """Phase ④: write + commit the frozen capture, resume the job."""
        t_start = ctx.stats.pop("t_start", time.perf_counter())
        if self.mode == "sync":
            try:
                path = self._write(ctx)                       # ④ write+commit
            except Exception:
                self.registry.exit_all("dump", False)
                raise
            ctx.stats["total_s"] = time.perf_counter() - t_start
            self.device_plugin.lock.unlock()                  # resume
            self.registry.exit_all("dump", True)
            self.last_stats = dict(ctx.stats)
            self._write_error = None               # last dump is clean
            self.last_commit_step = ctx.step
            return path

        # async: resume immediately, write in background (CheckFreq-style)
        self.device_plugin.lock.unlock()
        ctx.stats["locked_total_s"] = time.perf_counter() - t_start
        path = self._snapshot_path(ctx.step)

        # the writer thread has its own span context: hand it the
        # caller's (job attribution survives the async handoff)
        obs_ctx = obs_trace.current_context()

        def writer():
            with obs_trace.context(**obs_ctx):
                try:
                    self._write(ctx)
                    self._write_error = None       # last dump is clean
                    self.last_commit_step = ctx.step
                    self.registry.exit_all("dump", True)
                except BaseException as e:
                    self._pending_err.append(e)
                    # surface immediately: a silently-failed async dump
                    # must not look like a committed image to anyone
                    # polling stats
                    self._write_error = repr(e)
                    self.last_stats["write_error"] = repr(e)
                    self.registry.exit_all("dump", False)

        # publish the stats snapshot BEFORE the writer starts: the thread
        # keeps mutating ctx.stats (and on failure writes write_error into
        # self.last_stats), so copying after start would race both ways
        self.last_stats = dict(ctx.stats)
        self._pending = threading.Thread(target=writer, daemon=True,
                                         name="repro-async-writer")
        self._pending_ctx = ctx
        self._pending.start()
        return path

    def _snapshot_path(self, step: int) -> str:
        from repro.core.snapshot_io import snapshot_dir
        return snapshot_dir(self.run_dir, step)

    # ----------------------------------------------- concurrent capture
    def begin_concurrent(self, step: int) -> "ConcurrentCapture":
        """Start a soft-freeze capture (PhoenixOS-style validated
        speculation).

        Pin pause: quiesce the capture boundary (device lock + stream
        drain), pin the state tree (strong refs + identities) and start
        dirty tracking, then *resume the job*.  A background thread
        speculatively captures the pinned shards into an open stripe set
        while the step loop keeps running.  ``handle.finalize()`` takes
        the short validate pause: drain again, re-hash dirtied entries
        against the speculated per-chunk content hashes, re-capture only
        the invalidated ones, and commit — the committed image is the
        state at the *validate* pause, bit-exact vs a sync dump taken
        there.  Raises :class:`CheckpointAborted` (job keeps running, no
        image) on lock timeout or an unsafe op in flight.
        """
        if self._provider is None:
            raise RuntimeError("no state provider attached")
        if self.options.capture != "concurrent":
            from repro.api.options import OptionsError
            raise OptionsError(
                "begin_concurrent() requires "
                "CheckpointOptions(capture='concurrent'); "
                f"these options say capture={self.options.capture!r}")
        if self._concurrent is not None:
            self._concurrent.finalize()          # settle the previous one
        self.wait_pending()
        if self._lazy is not None:
            self.restore_barrier()

        _count_trigger()
        ctx = HookContext("dump", step)
        ctx.roots = self._provider()
        self.registry.init_all("dump")
        ctx.stats["t_begin"] = time.perf_counter()
        try:
            with obs_trace.span("dump.pause", step=step, phase="pin"):
                self.registry.run(Hook.PAUSE_DEVICES, ctx)  # pin pause
        except LockTimeout as e:
            self.registry.exit_all("dump", False)
            raise CheckpointAborted(str(e)) from e
        except UnsafeOpInFlight as e:
            self.device_plugin.lock.unlock()
            self.registry.exit_all("dump", False)
            raise CheckpointAborted(str(e)) from e
        except Exception:
            self.device_plugin.lock.unlock()
            self.registry.exit_all("dump", False)
            raise
        try:
            tracker = DirtyTracker()
            pinned = self.device_plugin.flatten_keys(ctx.roots)
            tracker.pin(pinned)
            self.device_plugin.begin_tracking(tracker)
            writer = self._make_writer(step)
        except Exception:
            self.device_plugin.end_tracking()
            self.device_plugin.lock.unlock()
            self.registry.exit_all("dump", False)
            raise
        handle = ConcurrentCapture(self, ctx, writer, pinned, tracker)
        self.device_plugin.lock.unlock()                   # job resumes
        ctx.stats["pin_pause_s"] = (time.perf_counter()
                                    - ctx.stats["t_begin"])
        ctx.stats["pin_lock_s"] = ctx.stats.pop("lock_s", 0.0)
        self._concurrent = handle
        handle._start()
        return handle

    @property
    def concurrent_capture(self) -> Optional["ConcurrentCapture"]:
        """The in-flight soft-freeze capture handle, if any."""
        return self._concurrent

    def _make_writer(self, step: int) -> SnapshotWriter:
        opts = self.options
        prev_manifest = None
        if self.incremental:
            # parent = newest step strictly below the one being dumped: a
            # re-dump of an existing step (checkpoint-on-signal right
            # after a periodic dump of the same step) must never use the
            # image it is about to overwrite as its own parent — the
            # locations would point at a pack the commit just replaced
            prev_steps = [s for s in self.store.list_steps()
                          if s < step]
            if prev_steps:
                prev_manifest = self.store.manifest(prev_steps[-1])
        return SnapshotWriter(self.run_dir, step,
                              host_id=jax.process_index(),
                              compress=self.compress,
                              prev_manifest=prev_manifest,
                              pack_format=opts.pack_format,
                              chunk_bytes=opts.chunk_mb << 20,
                              stripes=opts.stripes,
                              io_threads=opts.effective_io_threads())

    def _writer_stats(self, ctx: HookContext, writer: SnapshotWriter) -> None:
        ctx.stats["written_bytes"] = float(writer.written_bytes)
        ctx.stats["reused_bytes"] = float(writer.reused_bytes)
        # pipeline stage timings (thread-time, so compress_s + io_s
        # can legitimately exceed write_s when stages overlap)
        ctx.stats["compress_s"] = writer.compress_s
        ctx.stats["io_s"] = writer.io_s
        stripe_bytes = writer.stripe_bytes
        if stripe_bytes and max(stripe_bytes) > 0:
            ctx.stats["stripe_utilization"] = (
                min(stripe_bytes) / max(stripe_bytes))

    def _write(self, ctx: HookContext) -> str:
        t0 = time.perf_counter()
        writer = self._make_writer(ctx.step)
        try:
            with obs_trace.span("dump.write", step=ctx.step,
                                mode=self.mode):
                writer.write_states(ctx.device_snapshot)
                writer.write_host_state(ctx.host_state)
                t_serialize = time.perf_counter() - t0
                ctx.stats["host_bytes"] = float(
                    len(pack_host_blob(ctx.host_state)))
                path = writer.commit(topology=mesh_fingerprint(self.mesh),
                                     stats=ctx.stats,
                                     extra={"warnings": ctx.warnings,
                                            "mode": self.mode,
                                            "capture": "sync",
                                            "incremental": self.incremental})
            # commit() drains the pipeline and fsyncs; only now are the
            # stage timings and reuse accounting final (so these live in
            # last_stats, not in the manifest's embedded stats)
            ctx.stats["write_s"] = time.perf_counter() - t0
            ctx.stats["serialize_s"] = t_serialize
            self._writer_stats(ctx, writer)
        except Exception:
            writer.abort()
            raise
        self._after_commit(ctx, path)
        return path

    def _after_commit(self, ctx: HookContext, path: str) -> str:
        if self.replicator is not None:
            with obs_trace.span("dump.replicate", step=ctx.step):
                t_rep = time.perf_counter()
                self.replicator.push(self.run_dir, ctx.step)
                ctx.stats["replicate_s"] = time.perf_counter() - t_rep
            # replication counters (files/bytes copied vs skipped for the
            # dir replicator, chunks/bytes sent vs reused for the delta
            # one) ride along in the dump stats under a replica_ prefix
            # and mirror into the metrics registry; a replicator without
            # last_stats used to drop them invisibly — warn once instead
            obs_metrics.counter_add("replica.push_count")
            # the Replicator protocol's `stats` property; fall back to the
            # legacy `last_stats` attribute for third-party replicators
            rep_stats = getattr(self.replicator, "stats", None)
            if not isinstance(rep_stats, dict):
                rep_stats = getattr(self.replicator, "last_stats", None)
            if rep_stats is None:
                obs_metrics.counter_add("replica.missing_stats")
                obs_metrics.warn_once(
                    f"replicator-no-stats:{type(self.replicator).__name__}",
                    f"replicator {type(self.replicator).__name__} exposes "
                    f"no last_stats; replication counters for step "
                    f"{ctx.step} (and later dumps) are not recorded")
                rep_stats = {}
            for k, v in rep_stats.items():
                if isinstance(v, (int, float)):
                    ctx.stats[f"replica_{k}"] = v
                    obs_metrics.counter_add(f"replica.{k}", v)
        obs_metrics.counter_add("dump.count")
        obs_metrics.counter_add("dump.bytes_written",
                                ctx.stats.get("written_bytes", 0.0))
        obs_metrics.counter_add("dump.bytes_deduped",
                                ctx.stats.get("reused_bytes", 0.0))
        if "frozen_s" in ctx.stats:
            obs_metrics.observe("dump.frozen_s", ctx.stats["frozen_s"])
        obs_journal.emit("dump", "commit", step=ctx.step,
                         bytes=ctx.stats.get("written_bytes"),
                         frozen_s=ctx.stats.get("frozen_s"))
        if chaos_hooks.INJECTOR is not None:
            # chaos: lost-writeback site — the image is committed (and
            # replicated), so an injected local corruption here models a
            # dropped fsync that only the next restore can observe
            chaos_hooks.fire("engine.dump_done", run_dir=self.run_dir,
                             step=ctx.step, path=path)
        if self.keep:
            self.store.gc(self.keep)
        return path

    def wait_pending(self, timeout_s: Optional[float] = None) -> None:
        """Join the async background writer.

        ``timeout_s=None`` blocks until it finishes (historical
        behaviour).  With a timeout, a writer still running past the
        deadline raises :class:`PendingWriteStalled` instead of hanging
        forever (chaos ``degraded_io`` can wedge a writer indefinitely);
        the thread stays joinable so a later call can still reap it."""
        if self._pending is not None:
            t0 = time.perf_counter()
            step = (self._pending_ctx.step
                    if self._pending_ctx is not None else None)
            with obs_trace.span("dump.wait_pending", step=step) as sp:
                self._pending.join(timeout_s)
                if self._pending.is_alive():
                    waited = time.perf_counter() - t0
                    # the stall must be visible in the journal, not only
                    # as the raised exception
                    sp.set(stalled=True, waited_s=waited)
                    obs_metrics.observe("dump.pending_stall_s", waited)
                    obs_journal.emit("dump", "pending_stall", step=step,
                                     waited_s=waited, timeout_s=timeout_s)
                    raise PendingWriteStalled(step, waited)
            self._pending = None
            ctx, self._pending_ctx = self._pending_ctx, None
            if ctx is not None and not self._pending_err:
                # fold the background writer's stage timings (write_s,
                # written_bytes, compress_s, io_s, ...) into last_stats
                # now that the thread is joined — async dumps otherwise
                # never publish their write-stage stats
                self.last_stats.update(ctx.stats)
        if self._pending_err:
            # drain *every* queued failure, not just the newest: an older
            # failed dump must never be masked by a newer successful one
            errs = list(self._pending_err)
            self._pending_err.clear()
            msg = "; ".join(repr(e) for e in errs)
            self._write_error = msg
            self.last_stats["write_error"] = msg
            if len(errs) > 1:
                raise RuntimeError(
                    f"{len(errs)} async snapshot writes failed: {msg}"
                ) from errs[0]
            raise errs[0]

    @property
    def write_error(self) -> Optional[str]:
        """repr of the most recent async write failure (None if the last
        background dump committed cleanly)."""
        return self._write_error

    # ------------------------------------------------------------ restore
    def _verify_reader(self, reader, lazy: bool) -> None:
        """Pre-restore image check: eager verifies every entry; lazy
        verifies the critical set (plus the blobs read eagerly) so the
        job can resume before the cold entries are even read — those keep
        the same corruption guarantee because every background chunk read
        re-checks its stored CRC."""
        if lazy:
            from repro.core.lazy import critical_pack_names, split_schedule
            critical, _ = split_schedule(reader,
                                         self.options.critical_states)
            names = critical_pack_names(reader, critical)
        else:
            names = list(reader.manifest["locations"])
        sizes = reader.manifest.get("entry_bytes", {})
        with obs_trace.span("restore.verify", entries=len(names),
                            bytes=sum(int(sizes.get(n, 0)) for n in names)):
            reader.verify_entries(names)

    def _make_healer(self, step: int):
        """Background-stream heal hook: re-pull the image (and its delta
        chain) from the replica, so a torn background chunk is repaired
        in place instead of killing the stream."""
        rep = self.replicator
        if rep is None or not hasattr(rep, "pull"):
            return None

        def heal(state: str, path: str, exc: BaseException) -> bool:
            try:
                manifest = self.store.manifest(step)
                steps = sorted(self.store.referenced_steps(manifest)
                               | {step})
            except Exception:
                steps = [step]
            healed = False
            for s in steps:
                try:
                    if rep.pull(self.run_dir, s) is not None:
                        healed = True
                except Exception:
                    continue
            return healed

        return heal

    def _abandon_lazy(self) -> None:
        """A newer restore supersedes any still-streaming one: cancel it
        and wait for the thread to stop (its reader is closed and its pin
        released by the stream's own cleanup).  Errors are not raised —
        the superseding restore is frequently the retry path."""
        mat, self._lazy = self._lazy, None
        self._lazy_ctx, self._lazy_step = None, None
        if mat is not None and not mat.done:
            mat.cancel()
            mat.wait_done(timeout=60.0)

    def restore(self, step: Optional[int] = None, mesh=None,
                shardings: Optional[Dict[str, Any]] = None,
                verify: Optional[bool] = None,
                wait: Optional[str] = None) -> Dict[str, Any]:
        """Unified restore.  Returns {state_name: nested-dict pytree}; host
        state is pushed back through the registered CallbackPlugins.

        With ``options.restore_mode == "lazy"`` (or ``wait="critical"``)
        the call returns as soon as the critical set is placed; the
        remaining entries stream in the background and
        :meth:`restore_barrier` joins them.  ``wait="all"`` forces a full
        materialization before returning (eager restores always behave
        this way)."""
        if verify is None:
            verify = self.options.verify_restore
        if wait not in (None, "critical", "all"):
            raise ValueError(f"wait must be 'critical' or 'all', "
                             f"got {wait!r}")
        # wait="critical" opts a single call into the lazy machinery even
        # under eager options (per-call resume-before-read)
        lazy = self.options.restore_mode == "lazy" or wait == "critical"
        if wait is None:
            wait = "critical" if lazy else "all"
        self.wait_pending()
        self._abandon_lazy()
        t_restore0 = time.perf_counter()
        io_threads = self.options.effective_io_threads()
        # Hold the store lock for the whole critical phase so a gc running
        # in another thread of THIS process (sharing this SnapshotStore,
        # e.g. a concurrent checkpoint with keep=N) cannot delete a step
        # or a delta-chain parent pack out from under the scan/reads.
        # The lazy background stream runs *outside* the lock — it pins its
        # step instead, so gc skips it without blocking behind a
        # deliberately long-running restore.  A gc from a different
        # process (or a second store instance on the run_dir) is not
        # serialized by this lock — the newest-valid scan tolerates
        # vanishing images by falling back, but an explicitly requested
        # step may still fail mid-read there.
        sp_crit = obs_trace.span("restore.critical",
                                 mode="lazy" if lazy else "eager")
        with sp_crit, self.store.lock:
            steps = self.store.list_steps()
            if step is None:
                # newest *valid* image: fall back past torn/corrupt images
                # and past steps whose lazy background stream died (the
                # quarantine — a retry must not pick the same bad image)
                for s in reversed(steps):
                    if s in self._quarantined:
                        continue
                    reader = None
                    try:
                        reader = self.store.reader(s, verify=verify,
                                                   io_threads=io_threads)
                        if verify:
                            self._verify_reader(reader, lazy)
                        step = s
                        break
                    except Exception:
                        if reader is not None:
                            reader.close()
                        continue
                else:
                    if self.replicator is not None:
                        got = self.replicator.pull_latest(self.run_dir)
                        if got is not None:
                            self._quarantined.discard(got)
                            out = self.restore(step=got, mesh=mesh,
                                               shardings=shardings,
                                               verify=verify, wait=wait)
                            self.last_stats["restored_from_replica"] = True
                            return out
                    raise FileNotFoundError(
                        f"no restorable snapshot under {self.run_dir}")
            else:
                # explicitly requested step: verify with the same rigor as
                # the newest-valid scan — a torn image must raise, not
                # restore garbage (historically this path skipped
                # verify_all()).
                reader = self.store.reader(step, verify=verify,
                                           io_threads=io_threads)
                if verify:
                    try:
                        self._verify_reader(reader, lazy)
                    except Exception:
                        reader.close()
                        raise

            sp_crit.set(step=step)
            ctx = HookContext("restore", step)
            ctx.reader = reader
            ctx.manifest = reader.manifest
            ctx.target_mesh = mesh if mesh is not None else self.mesh
            ctx.target_shardings = shardings or {}
            ctx.restore_threads = self.options.restore_threads or io_threads
            ctx.lazy = lazy
            if lazy:
                ctx.critical_specs = self.options.critical_states
                self.store.pin(step)
                ctx.lazy_reopen = (
                    lambda s=step: self.store.reader(
                        s, verify=verify, io_threads=io_threads))
                ctx.lazy_heal = self._make_healer(step)
                ctx.lazy_on_done = (lambda s=step: self.store.unpin(s))
            self.registry.init_all("restore")
            materializer = None
            try:
                ctx.host_state = reader.host_state()
                self.registry.run(Hook.RESTORE_EXT_STATE, ctx)
                self.registry.run(Hook.UPDATE_TOPOLOGY_MAP, ctx)
                self.registry.run(Hook.RESUME_DEVICES_LATE, ctx)
                materializer = getattr(ctx, "materializer", None)
            except Exception:
                self.registry.exit_all("restore", False)
                ctx.stats.update(reader.io_stats())
                reader.close()
                if lazy:
                    self.store.unpin(step)
                raise
            ctx.stats.update(reader.io_stats())   # read_s, decompress_s
            if materializer is None:
                reader.close()                    # eager: image fully read
                if lazy:
                    self.store.unpin(step)        # backend without lazy
        self.registry.exit_all("restore", True)
        ctx.stats["restore_critical_s"] = time.perf_counter() - t_restore0
        ctx.stats["restore_mode"] = "lazy" if lazy else "eager"
        obs_metrics.counter_add("restore.count")
        obs_metrics.observe("restore.critical_s",
                            ctx.stats["restore_critical_s"])
        obs_journal.emit("restore", "resumed", step=step,
                         mode=ctx.stats["restore_mode"])
        self.last_stats = dict(ctx.stats)
        self.last_stats["topology_mode"] = ctx.topology_map.get("mode")
        self._last_restored = ctx.restored
        if materializer is not None:
            self._lazy = materializer
            self._lazy_ctx = ctx
            self._lazy_step = step
            materializer.start()                  # stream the cold tail
            if wait == "all":
                return self.restore_barrier()
        return ctx.restored

    def restore_barrier(self) -> Optional[Dict[str, Any]]:
        """Join the background restore stream.

        Blocks until every lazily-scheduled entry has landed, then
        returns the complete restored tree.  If the stream died (torn
        chunk that could not be healed, vanished pack), raises
        :class:`repro.core.lazy.LazyRestoreError`, quarantines the step,
        and a retried :meth:`restore` falls back to an eager restore of
        the previous committed image.  A no-op after eager restores."""
        mat = self._lazy
        if mat is None:
            return self._last_restored
        try:
            mat.join()
        except BaseException:
            if self._lazy_step is not None:
                self._quarantined.add(self._lazy_step)
            self._lazy, self._lazy_ctx, self._lazy_step = None, None, None
            raise
        for k in ("background_s", "background_bytes",
                  "background_entries", "healed_entries"):
            self.last_stats[k] = mat.stats.get(k, 0.0)
        self.last_stats["restore_background_s"] = mat.stats["background_s"]
        restored = self._lazy_ctx.restored
        self._last_restored = restored
        self._lazy, self._lazy_ctx, self._lazy_step = None, None, None
        return restored

    @property
    def lazy_pending(self) -> bool:
        """True while a background restore stream is still outstanding."""
        return self._lazy is not None

    @staticmethod
    def retree(template: PyTree, raw_tree: Any) -> PyTree:
        """Rebuild `template`'s pytree types (e.g. OptState dataclasses)
        from a raw nested-dict restore view."""
        from repro.core.device_plugin import flatten_with_paths
        flat = flatten_with_paths(template)
        raw = flatten_with_paths(raw_tree)
        missing = set(flat) - set(raw)
        if missing:
            raise KeyError(f"snapshot missing leaves: {sorted(missing)[:5]}")
        _, treedef = jax.tree_util.tree_flatten(template)
        return jax.tree_util.tree_unflatten(
            treedef, [raw[k] for k in flat])

    def restore_into(self, template: PyTree, state: str = "train_state",
                     step: Optional[int] = None, mesh=None,
                     shardings: Optional[PyTree] = None,
                     wait: Optional[str] = None) -> PyTree:
        """Restore one state into the caller's pytree structure (types
        preserved — e.g. OptState dataclasses).

        In lazy mode the typed reassembly needs every template leaf, so
        if the background stream has not yet landed them all this joins
        it (`restore_barrier`) before rebuilding — callers that want the
        resume-before-read overlap should use :meth:`restore` with
        ``wait="critical"`` and :meth:`retree` the cold subtrees after
        the barrier (see ``runtime.Trainer.restore``)."""
        restored = self.restore(step=step, mesh=mesh,
                                shardings={state: shardings}
                                if shardings is not None else None,
                                wait=wait)
        if self._lazy is not None:
            # always join the stream: even if every template leaf already
            # landed, leaving the materializer outstanding would hand the
            # caller a "complete" tree with lazy_pending still True
            restored = self.restore_barrier()
        return self.retree(template, restored[state])

    def latest_step(self) -> Optional[int]:
        return self.store.latest_step()


class ConcurrentCapture:
    """Handle for one in-flight soft-freeze capture.

    Lifecycle: ``engine.begin_concurrent(step)`` returns this with the
    speculation thread running and the job resumed; the caller steps
    freely (polling :attr:`speculation_done`), then calls
    :meth:`finalize` for the validate/patch pause and the atomic commit,
    or :meth:`abort` to discard everything.  The committed image is
    bit-exact with the live state at the validate pause — speculation
    that survived validation was, by the content hashes, already
    identical to it.
    """

    def __init__(self, engine: SnapshotEngine, ctx: HookContext,
                 writer: SnapshotWriter, pinned: Dict[str, Any],
                 tracker: DirtyTracker):
        self._engine = engine
        self.ctx = ctx
        self._writer = writer
        self._pinned = pinned
        self._tracker = tracker
        self._stop = threading.Event()
        self._spec_done = threading.Event()
        self._spec_err: Optional[BaseException] = None
        self._speculated: set = set()
        self._done = False
        self._obs_ctx = obs_trace.current_context()
        self._thread = threading.Thread(target=self._speculate,
                                        name="repro-spec-capture",
                                        daemon=True)

    def _start(self) -> None:
        self._thread.start()

    # ------------------------------------------------------------- state
    @property
    def step(self) -> int:
        return self.ctx.step

    @property
    def stats(self) -> Dict[str, Any]:
        return self.ctx.stats

    @property
    def speculation_done(self) -> bool:
        """True once the background pass over the pinned tree finished
        (finalize() after this point pays the smallest pause)."""
        return self._spec_done.is_set()

    def wait_speculated(self, timeout: Optional[float] = None) -> bool:
        return self._spec_done.wait(timeout)

    # -------------------------------------------------------- speculation
    def _speculate(self) -> None:
        backend = self._engine.device_plugin
        t0 = time.perf_counter()
        with obs_trace.context(**self._obs_ctx), \
                obs_trace.span("dump.speculate", step=self.ctx.step) as sp:
            try:
                for key, leaf in self._pinned.items():
                    if self._stop.is_set():
                        break
                    if chaos_hooks.INJECTOR is not None:
                        # chaos: mutation-storm site — a handler may mutate
                        # the live leaf mid-speculation (it must call note())
                        chaos_hooks.fire("engine.speculate", key=key,
                                         leaf=leaf, note=self._tracker.note,
                                         step=self.ctx.step,
                                         run_dir=self._engine.run_dir)
                    state, path = key.split("::", 1)
                    try:
                        entry = backend.capture_entry(leaf)
                    except Exception:
                        # donated away / deleted under us: the live value is
                        # captured at the validate pause instead
                        self._tracker.note(key)
                        continue
                    self._writer.put_state_entry(state, path, entry)
                    self._speculated.add(key)
                if not self._stop.is_set():
                    # drain the pack pipeline while the job still runs: once
                    # speculation_done is set, finalize()'s own flush is a
                    # no-op and the validate pause shrinks to hash + commit
                    self._writer.flush()
            except BaseException as e:
                self._spec_err = e
            finally:
                self.ctx.stats["speculate_s"] = time.perf_counter() - t0
                self.ctx.stats["speculated_entries"] = len(self._speculated)
                sp.set(entries=len(self._speculated))
                self._spec_done.set()

    # ----------------------------------------------------------- finalize
    def finalize(self) -> str:
        """Validate pause: quiesce, re-hash dirtied entries against the
        speculated chunk hashes, re-capture only actual mismatches, dump
        host state, commit atomically, resume.  Returns the snapshot
        directory.  Raises CheckpointAborted (no image, job running) on
        lock timeout / unsafe op in flight."""
        # the caller's span context at begin (job, trigger) also marks
        # the validate-side spans, wherever finalize is called from
        with obs_trace.context(**self._obs_ctx):
            return self._finalize()

    def _finalize(self) -> str:
        if self._done:
            raise RuntimeError("concurrent capture already finalized")
        eng = self._engine
        ctx = self.ctx
        backend = eng.device_plugin
        t_val = time.perf_counter()
        try:
            ctx.roots = eng._provider()
            with obs_trace.span("dump.pause", step=ctx.step,
                                phase="validate"):
                eng.registry.run(Hook.PAUSE_DEVICES, ctx)  # validate pause
        except LockTimeout as e:
            self._cleanup(unlock=False)
            raise CheckpointAborted(str(e)) from e
        except UnsafeOpInFlight as e:
            self._cleanup(unlock=True)
            raise CheckpointAborted(str(e)) from e
        except Exception:
            self._cleanup(unlock=True)
            raise
        try:
            with obs_trace.span("dump.validate", step=ctx.step) as sp_val:
                self._stop.set()
                self._thread.join()
                if self._spec_err is not None:
                    raise self._spec_err
                self._writer.flush()    # speculated chunk records final
                # the post-lock tree is the commit point
                ctx.roots = eng._provider()
                live = backend.flatten_keys(ctx.roots)
                if chaos_hooks.INJECTOR is not None:
                    # chaos: validate site — burst handlers restore their
                    # mutations here so the job's own trajectory is intact
                    chaos_hooks.fire("engine.validate", step=ctx.step,
                                     run_dir=eng.run_dir)
                dirty = self._tracker.dirty_keys(live)
                sp_val.set(dirty=len(dirty))
            recaptured = recaptured_bytes = 0
            with obs_trace.span("dump.patch", step=ctx.step) as sp_patch:
                for key, leaf in live.items():
                    state, path = key.split("::", 1)
                    is_array = (hasattr(leaf, "shape")
                                and hasattr(leaf, "dtype"))
                    if (key in dirty or key not in self._speculated
                            or not is_array):
                        nb = self._writer.reput_state_entry(
                            state, path, backend.capture_entry(leaf))
                        if nb:
                            recaptured += 1
                            recaptured_bytes += nb
                for key in self._pinned:
                    if key not in live:  # structural drift: entry gone
                        state, path = key.split("::", 1)
                        self._writer.drop_state_entry(state, path)
                sp_patch.set(recaptured=recaptured)
            eng.registry.run(Hook.DUMP_EXT_STATE, ctx)
            self._writer.write_host_state(ctx.host_state)
            ctx.stats["host_bytes"] = float(
                len(pack_host_blob(ctx.host_state)))
            ctx.stats["dirty_entries"] = len(dirty)
            ctx.stats["recaptured_entries"] = recaptured
            ctx.stats["recaptured_bytes"] = float(recaptured_bytes)
            ctx.stats["superseded_bytes"] = float(
                self._writer.superseded_bytes)
            ctx.stats["validate_pause_s"] = time.perf_counter() - t_val
            ctx.stats["frozen_s"] = (ctx.stats["pin_pause_s"]
                                     + ctx.stats["validate_pause_s"])
            path = self._writer.commit(
                topology=mesh_fingerprint(eng.mesh), stats=ctx.stats,
                extra={"warnings": ctx.warnings,
                       "mode": eng.mode,
                       "incremental": eng.incremental,
                       "capture": "concurrent",
                       "capture_stats": {
                           k: ctx.stats[k] for k in (
                               "pin_pause_s", "validate_pause_s",
                               "frozen_s", "speculate_s",
                               "speculated_entries", "dirty_entries",
                               "recaptured_entries", "recaptured_bytes",
                               "superseded_bytes")
                           if k in ctx.stats}})
            self._writer_post_commit_stats(ctx)
        except Exception:
            self._cleanup(unlock=True)
            raise
        # the fsync/rename is part of the pause the caller observed
        ctx.stats["validate_pause_s"] = time.perf_counter() - t_val
        ctx.stats["frozen_s"] = (ctx.stats["pin_pause_s"]
                                 + ctx.stats["validate_pause_s"])
        ctx.stats["locked_total_s"] = ctx.stats["frozen_s"]
        eng.device_plugin.lock.unlock()                    # resume
        backend.end_tracking()
        self._tracker.reset()
        eng.registry.exit_all("dump", True)
        t_begin = ctx.stats.pop("t_begin", t_val)
        ctx.stats["total_s"] = time.perf_counter() - t_begin
        eng._concurrent = None
        self._done = True
        eng._after_commit(ctx, path)
        eng.last_stats = dict(ctx.stats)
        eng._write_error = None
        eng.last_commit_step = ctx.step
        return path

    def _writer_post_commit_stats(self, ctx: HookContext) -> None:
        eng = self._engine
        ctx.stats["write_s"] = ctx.stats.get("speculate_s", 0.0)
        eng._writer_stats(ctx, self._writer)

    # -------------------------------------------------------------- abort
    def abort(self) -> None:
        """Discard the capture: stop speculation, delete the open stripe
        set, resume tracking-free.  The job never observes it."""
        if self._done:
            return
        self._cleanup(unlock=False)

    def _cleanup(self, unlock: bool) -> None:
        eng = self._engine
        self._stop.set()
        self._thread.join(timeout=30.0)
        try:
            self._writer.abort()
        except Exception:
            pass
        eng.device_plugin.end_tracking()
        self._tracker.reset()
        if unlock:
            try:
                eng.device_plugin.lock.unlock()
            except Exception:
                pass
        eng.registry.exit_all("dump", False)
        eng._concurrent = None
        self._done = True
