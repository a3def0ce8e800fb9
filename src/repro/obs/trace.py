"""Phase tracing: nested spans with thread attribution, zero-overhead
when disabled.

Mirrors ``repro.chaos.hooks``: a module-global ``TRACER`` that is
``None`` until ``install()``.  ``span()`` is safe to call unconditionally
on warm paths — when no tracer is installed it returns a shared no-op
singleton (one function call, one attribute load, no per-call state).
Hot per-chunk paths (the pack writer's worker loops) additionally guard
with ``if trace.TRACER is not None and trace.TRACER.detail:`` so the
disabled cost there is a single pointer read.

Spans nest per-thread: a span opened while another is live on the same
thread records that span as its parent, which is what makes the pack
pipeline legible — each compress/append worker carries its own stack, and
the exporter lays them out as Chrome trace rows keyed by thread name.

``record()`` emits a retroactive span from explicit timestamps; the
orchestrator's ``RecoveryLog`` uses it so every recovery phase
(detect/transfer/schedule/restore/background/replay) appears in the
trace as a first-class span instead of parallel bookkeeping.

While a tracer is installed, two bridges to JAX are live:

* every span opened through ``Tracer.begin`` also holds a
  ``jax.profiler.TraceAnnotation`` of its name, so a ``jax.profiler``
  capture shows the program's spans as host events on the trace's own
  clock, beside the device operations (``record()`` spans are not
  bridged: their time has already passed);
* a ``jax.monitoring`` duration listener turns JAX's jit phases into
  retroactive ``jit.trace`` / ``jit.lower`` / ``jit.compile`` spans
  (``jit.compile`` is a backend compile or a compile-cache load) and
  counts the latter under the ``jit.compiles`` metric.

``install()`` imports JAX and registers both; ``uninstall()`` removes
them, so with no tracer installed no listener exists and no annotation
is made.

This module deliberately imports nothing from ``repro`` outside
``repro.obs`` (JAX only lazily, at ``install()``) so every layer
(serialization, transfer, orchestrator) can depend on it without cycles.
"""
from __future__ import annotations

import itertools
import threading
import time
from typing import Any, Callable, Dict, List, Optional

from repro.obs import metrics as _metrics

TRACER: Optional["Tracer"] = None
# jax.profiler.TraceAnnotation while a tracer is installed, else None
_ANNOTATION: Optional[Callable[[str], Any]] = None
# the jax.monitoring listener while registered, else None
_JIT_LISTENER: Optional[Callable[..., None]] = None

# jax.monitoring duration events (jax/_src/dispatch.py) -> span name
JIT_EVENTS: Dict[str, str] = {
    "/jax/core/compile/jaxpr_trace_duration": "jit.trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "jit.lower",
    "/jax/core/compile/backend_compile_duration": "jit.compile",
}

# span name -> (layer, description); the stable schema the docs table and
# the exporter's class filter (`repro events --class`) key off.  A span's
# event class is its name's first dotted component.
SPAN_SCHEMA: Dict[str, tuple] = {
    "dump.pause": ("engine", "device quiesce: PAUSE_DEVICES hooks"),
    "dump.capture": ("engine", "device->host state capture"),
    "dump.ext_state": ("engine", "host-side external state dump"),
    "dump.write": ("engine", "serialize + commit to storage"),
    "dump.wait_pending": ("engine", "join of the async writer thread"),
    "dump.speculate": ("engine", "concurrent capture: speculative pass"),
    "dump.validate": ("engine", "concurrent capture: validate pause"),
    "dump.patch": ("engine", "concurrent capture: dirty-entry recapture"),
    "dump.commit": ("engine", "manifest + meta commit"),
    "dump.replicate": ("engine", "post-commit replication push"),
    "pack.compress": ("serialization", "one chunk through the codec "
                                       "(detail mode only)"),
    "pack.append": ("serialization", "one chunk appended to its stripe "
                                     "(detail mode only)"),
    "pack.flush": ("serialization", "pipeline drain barrier"),
    "restore.critical": ("engine", "restore() critical path: scan, read, "
                                   "place, resume"),
    "restore.critical_place": ("engine", "critical-set entry placement "
                                         "(inside restore.critical)"),
    "restore.verify": ("engine", "image CRC check before a restore "
                                 "(inside restore.critical)"),
    "restore.read": ("engine", "eager restore: read and decode every "
                               "entry (inside restore.critical)"),
    "restore.place": ("engine", "eager restore: rebuild every leaf, "
                                "start its host->device copy "
                                "(inside restore.critical)"),
    "restore.background": ("engine", "lazy background stream"),
    "restore.entry": ("engine", "one background entry "
                                "(detail mode only)"),
    "transfer.push": ("transfer", "full delta-replication push"),
    "transfer.round": ("transfer", "one pre-copy migration round "
                                   "(live or frozen residual)"),
    "transfer.negotiate": ("transfer", "CAS have/want round"),
    "transfer.ship": ("transfer", "missing chunks over the wire"),
    "transfer.materialize": ("transfer", "peer-side pack rebuild"),
    "recovery.detect": ("orchestrator", "interrupt -> noticed"),
    "recovery.transfer": ("orchestrator", "image pre-stage to new host"),
    "recovery.schedule": ("orchestrator", "noticed -> capacity found"),
    "recovery.restore": ("orchestrator", "restore start -> RUNNING"),
    "recovery.restore_background": ("orchestrator",
                                    "resume -> fully materialized"),
    "recovery.replay": ("orchestrator", "restored step -> caught up"),
    "fleet.boot": ("orchestrator", "image -> serving replica "
                                   "(the TTFT window)"),
    "fleet.serve": ("orchestrator", "bursty request trace against the "
                                    "fleet (autoscale inside)"),
    "train.step": ("runtime", "one training step: batch, dispatch, loss "
                              "on the host"),
    "train.sync": ("runtime", "the wait for the step's loss on the host "
                              "(inside train.step)"),
    "serve.step": ("runtime", "one decoded token: wait for tokens "
                              "older than the lag, dispatch the step "
                              "and its argmax, start the token's copy "
                              "to the host"),
    "serve.sync": ("runtime", "a wait for fetched tokens, one or several, "
                              "on the host (inside serve.step)"),
    "serve.cast": ("runtime", "build of the decode server's compute "
                              "copy of its weights, at the first prefill "
                              "or token after a load or restore"),
    "jit.trace": ("jax", "jaxpr trace of a jitted function "
                         "(retroactive)"),
    "jit.lower": ("jax", "jaxpr -> MLIR lowering (retroactive)"),
    "jit.compile": ("jax", "backend compile or compile-cache load "
                           "(retroactive)"),
}


class _NoopSpan:
    """Shared do-nothing span returned when no tracer is installed."""

    __slots__ = ()

    def __enter__(self) -> "_NoopSpan":
        return self

    def __exit__(self, *exc: Any) -> bool:
        return False

    def set(self, **attrs: Any) -> "_NoopSpan":
        return self


NOOP_SPAN = _NoopSpan()


class Span:
    """One live span; finished (and sunk) when its ``with`` block exits."""

    __slots__ = ("name", "attrs", "span_id", "parent_id", "thread",
                 "t_start", "t_end", "_tracer", "_ann")

    def __init__(self, tracer: "Tracer", name: str, attrs: Dict[str, Any],
                 span_id: int, parent_id: Optional[int]) -> None:
        self._tracer = tracer
        self.name = name
        self.attrs = attrs
        self.span_id = span_id
        self.parent_id = parent_id
        self.thread = threading.current_thread().name
        self.t_start = tracer.clock()
        self.t_end: Optional[float] = None
        self._ann: Any = None       # profiler annotation while open

    def set(self, **attrs: Any) -> "Span":
        self.attrs.update(attrs)
        return self

    def __enter__(self) -> "Span":
        return self

    def __exit__(self, exc_type: Any, exc: Any, tb: Any) -> bool:
        if exc_type is not None:
            self.attrs.setdefault("error", exc_type.__name__)
        self._tracer._finish(self)
        return False


class Tracer:
    """Collects spans; per-thread stacks give nesting, ``sink`` (set by
    the plane) forwards each finished span to the run journal."""

    def __init__(self, sink: Optional[Callable[[Span], None]] = None,
                 detail: bool = False,
                 clock: Callable[[], float] = time.perf_counter) -> None:
        self.sink = sink
        self.detail = detail       # opt-in per-chunk spans on hot paths
        self.clock = clock
        self.t0 = clock()
        self.spans: List[Span] = []
        self._lock = threading.Lock()
        self._tls = threading.local()
        self._ids = itertools.count(1)

    # ------------------------------------------------------------- stacks
    def _stack(self) -> List[Span]:
        st = getattr(self._tls, "stack", None)
        if st is None:
            st = self._tls.stack = []
        return st

    def _ctx(self) -> Dict[str, Any]:
        ctx = getattr(self._tls, "ctx", None)
        if ctx is None:
            ctx = self._tls.ctx = {}
        return ctx

    # -------------------------------------------------------------- spans
    def begin(self, name: str, attrs: Dict[str, Any]) -> Span:
        ctx = self._ctx()
        if ctx:
            merged = dict(ctx)
            merged.update(attrs)
            attrs = merged
        stack = self._stack()
        parent = stack[-1].span_id if stack else None
        sp = Span(self, name, attrs, next(self._ids), parent)
        ann = _ANNOTATION
        if ann is not None:
            sp._ann = ann(name)
            sp._ann.__enter__()
        stack.append(sp)
        return sp

    def _finish(self, sp: Span) -> None:
        sp.t_end = self.clock()
        if sp._ann is not None:
            sp._ann.__exit__(None, None, None)
            sp._ann = None
        stack = self._stack()
        if sp in stack:                      # tolerate exits out of order
            stack.remove(sp)
        with self._lock:
            self.spans.append(sp)
        if self.sink is not None:
            self.sink(sp)

    def record(self, name: str, t_start: float, t_end: float,
               attrs: Dict[str, Any]) -> Span:
        """Retroactive span from explicit (tracer-clock) timestamps."""
        sp = Span(self, name, dict(attrs), next(self._ids), None)
        sp.t_start = t_start
        sp.t_end = max(t_start, t_end)
        with self._lock:
            self.spans.append(sp)
        if self.sink is not None:
            self.sink(sp)
        return sp

    # ------------------------------------------------------------ context
    class _Ctx:
        __slots__ = ("_tracer", "_saved")

        def __init__(self, tracer: "Tracer", attrs: Dict[str, Any]) -> None:
            self._tracer = tracer
            ctx = tracer._ctx()
            self._saved = dict(ctx)
            ctx.update(attrs)

        def __enter__(self) -> "Tracer._Ctx":
            return self

        def __exit__(self, *exc: Any) -> bool:
            self._tracer._tls.ctx = self._saved
            return False


class _NoopCtx:
    __slots__ = ()

    def __enter__(self) -> "_NoopCtx":
        return self

    def __exit__(self, *exc: Any) -> bool:
        return False


_NOOP_CTX = _NoopCtx()


# ------------------------------------------------------------- module API
def span(name: str, **attrs: Any):
    """Open a span, or return the shared no-op when tracing is off."""
    tr = TRACER
    if tr is None:
        return NOOP_SPAN
    return tr.begin(name, attrs)


def record(name: str, t_start: float, t_end: float, **attrs: Any) -> None:
    """Emit a retroactive span (no-op when tracing is off)."""
    tr = TRACER
    if tr is not None:
        tr.record(name, t_start, t_end, attrs)


def context(**attrs: Any):
    """Attach attrs (e.g. ``job=...``) to every span opened on this
    thread inside the ``with`` block.  No-op when tracing is off."""
    tr = TRACER
    if tr is None:
        return _NOOP_CTX
    return Tracer._Ctx(tr, attrs)


def current_context() -> Dict[str, Any]:
    """Copy of the calling thread's span context — capture it before
    spawning a worker thread, re-apply inside with ``context(**saved)``
    so spans the worker emits keep e.g. their job attribution."""
    tr = TRACER
    if tr is None:
        return {}
    return dict(tr._ctx())


def _on_jax_duration(event: str, duration: float, **kw: Any) -> None:
    """jax.monitoring listener: a finished jit phase becomes a span that
    ends now on the tracer's clock and lasted ``duration``."""
    name = JIT_EVENTS.get(event)
    tr = TRACER
    if name is None or tr is None:
        return
    t_end = tr.clock()
    tr.record(name, t_end - duration, t_end,
              {"fun_name": kw.get("fun_name")})
    if name == "jit.compile":
        _metrics.counter_add("jit.compiles")


def install(tracer: Tracer) -> None:
    global TRACER, _ANNOTATION, _JIT_LISTENER
    if TRACER is not None and TRACER is not tracer:
        raise RuntimeError("a tracer is already installed; "
                           "uninstall it first")
    if _JIT_LISTENER is None:
        import jax.monitoring
        import jax.profiler
        jax.monitoring.register_event_duration_secs_listener(
            _on_jax_duration)
        _JIT_LISTENER = _on_jax_duration
        _ANNOTATION = jax.profiler.TraceAnnotation
    TRACER = tracer


def uninstall() -> None:
    global TRACER, _ANNOTATION, _JIT_LISTENER
    TRACER = None
    _ANNOTATION = None
    if _JIT_LISTENER is not None:
        import jax.monitoring
        jax.monitoring.unregister_event_duration_listener(_JIT_LISTENER)
        _JIT_LISTENER = None
