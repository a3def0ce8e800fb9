"""repro.obs — unified observability plane.

Covers the subsystem's acceptance criteria:

  * zero overhead when disabled: span()/counter_add()/emit() are no-ops
    (shared singletons, no per-call state), and a dump's ``last_stats``
    carries exactly the same keys with or without the plane installed;
  * spans nest deterministically, including across the pack writer's
    thread pool (detail mode) and async-writer / speculate / lazy
    worker threads (job context survives the handoff);
  * satellites: replicator counters route through the metrics registry
    with a one-time warning for a stats-less replicator;
    ``wait_pending`` stalls emit a span + histogram + journal event;
  * the run journal validates, exports to Chrome trace-event JSON, and
    filters by job / class; injected chaos faults land as journal
    events aligned with incident spans.
"""
import json
import threading
import time
import warnings

import numpy as np
import pytest

from repro.core import SnapshotEngine
from repro.core.engine import PendingWriteStalled
from repro.obs import export, journal, metrics, trace
from repro.obs.plane import ObservabilityPlane, observed


def make_state(n=4, kb=8):
    rng = np.random.default_rng(0)
    return {f"w{i}": rng.integers(0, 8, size=kb * 256).astype(np.float32)
            for i in range(n)}


# --------------------------------------------------------- disabled path
@pytest.fixture
def made(monkeypatch):
    """Counts of the ``Span``s and profiler annotations created."""
    import jax
    counts = {"span": 0, "annotation": 0}
    span_init = trace.Span.__init__
    annotation = jax.profiler.TraceAnnotation

    def counted_span_init(self, *a, **kw):
        counts["span"] += 1
        span_init(self, *a, **kw)

    def counted_annotation(*a, **kw):
        counts["annotation"] += 1
        return annotation(*a, **kw)

    monkeypatch.setattr(trace.Span, "__init__", counted_span_init)
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", counted_annotation)
    return counts


def test_disabled_plane_is_inert(tmp_path, made):
    """No plane installed: every module global is None, span() returns
    the one shared no-op singleton, and the other entry points return
    without touching any state.  An eager restore and a fresh jit
    create no ``Span`` and no profiler annotation, and no jit listener
    is registered."""
    import jax
    assert trace.TRACER is None
    assert trace._JIT_LISTENER is None and trace._ANNOTATION is None
    assert metrics.REGISTRY is None
    assert journal.JOURNAL is None
    spans = [trace.span("dump.pause", step=i) for i in range(32)]
    assert all(sp is trace.NOOP_SPAN for sp in spans)
    with trace.span("dump.capture") as sp:
        sp.set(anything=1)          # no-op, chainable
    assert trace.record("recovery.detect", 0.0, 1.0) is None
    assert trace.current_context() == {}
    metrics.counter_add("dump.count")
    metrics.gauge_set("pack.queue_depth", 3)
    metrics.observe("dump.frozen_s", 0.1)
    journal.emit("dump", "commit", step=1)
    with trace.context(job="j0"):
        assert trace.span("dump.pause") is trace.NOOP_SPAN
    eng = SnapshotEngine(str(tmp_path / "run"))
    eng.attach(lambda: {"train_state": make_state()})
    eng.checkpoint(1)
    SnapshotEngine(str(tmp_path / "run")).restore()
    jax.jit(lambda x: x * 3)(np.ones(2, np.float32)).block_until_ready()
    assert made == {"span": 0, "annotation": 0}


def test_last_stats_parity_disabled_vs_seed(tmp_path):
    """The instrumented dump path publishes bit-identical stats keys
    whether or not the plane was ever installed — no obs bookkeeping
    leaks into ``last_stats``."""
    state = make_state()

    def run(run_dir, plane):
        eng = SnapshotEngine(str(run_dir))
        eng.attach(lambda: {"train_state": state})
        if plane:
            with observed(str(run_dir / "obs_run")):
                eng.checkpoint(1)
        else:
            eng.checkpoint(1)
        return dict(eng.last_stats)

    st_off = run(tmp_path / "off", plane=False)
    st_on = run(tmp_path / "on", plane=True)
    assert sorted(st_off) == sorted(st_on)
    assert not any(k.startswith("obs") for k in st_off)
    # plane uninstalled cleanly
    assert trace.TRACER is None and metrics.REGISTRY is None


def test_install_is_exclusive(tmp_path):
    plane = ObservabilityPlane(str(tmp_path / "run"))
    plane.install()
    try:
        other = ObservabilityPlane(str(tmp_path / "run2"))
        with pytest.raises(RuntimeError, match="already installed"):
            other.install()
        other.journal.close()
    finally:
        plane.close()
    assert trace.TRACER is None and journal.JOURNAL is None


# ------------------------------------------------ restore, steps, jit
@pytest.mark.parametrize("backend", ["jax", "host"])
def test_eager_restore_splits_into_verify_read_place(tmp_path, backend):
    """One each of restore.verify, restore.read and restore.place per
    eager restore, children of restore.critical; the critical time is
    in last_stats and the histogram for eager restores too."""
    state = make_state(n=3)
    eng = SnapshotEngine(str(tmp_path / "run"))
    eng.attach(lambda: {"train_state": state})
    eng.checkpoint(1)
    tr, reg = trace.Tracer(), metrics.MetricsRegistry()
    trace.install(tr)
    metrics.install(reg)
    try:
        eng2 = SnapshotEngine(str(tmp_path / "run"), backend=backend)
        out = eng2.restore()
    finally:
        trace.uninstall()
        metrics.uninstall()
    np.testing.assert_array_equal(np.asarray(out["train_state"]["w1"]),
                                  state["w1"])
    (crit,) = [sp for sp in tr.spans if sp.name == "restore.critical"]
    kids = {sp.name: sp for sp in tr.spans if sp.parent_id == crit.span_id}
    assert sorted(kids) == ["restore.place", "restore.read",
                            "restore.verify"]
    assert kids["restore.verify"].attrs["entries"] >= 3
    # every array's bytes, plus the host-state and meta blobs
    assert kids["restore.verify"].attrs["bytes"] >= sum(
        v.nbytes for v in state.values())
    assert kids["restore.read"].attrs["entries"] == 3
    assert kids["restore.place"].attrs["leaves"] == 3
    assert (kids["restore.read"].t_end
            <= kids["restore.place"].t_start)
    assert eng2.last_stats["restore_critical_s"] > 0
    hist = reg.snapshot()["histograms"]["restore.critical_s"]
    assert hist["count"] == 1
    assert hist["sum"] == eng2.last_stats["restore_critical_s"]


def test_jit_phases_become_spans_and_are_counted():
    """A fresh jit under an installed tracer gives jit.trace, jit.lower
    and jit.compile spans and counts the compile; the cached call gives
    none; uninstall removes the listener."""
    import jax
    from jax._src import monitoring
    tr, reg = trace.Tracer(), metrics.MetricsRegistry()
    trace.install(tr)
    metrics.install(reg)
    try:
        assert trace._on_jax_duration in \
            monitoring.get_event_duration_listeners()
        f = jax.jit(lambda x: x * 2 + 1)
        x = np.ones(5, np.float32)
        t_call = tr.clock()
        f(x).block_until_ready()
        first = [sp for sp in tr.spans if sp.name.startswith("jit.")]
        n_spans = len(tr.spans)
        f(x).block_until_ready()
        assert len(tr.spans) == n_spans
    finally:
        trace.uninstall()
        metrics.uninstall()
    names = {sp.name for sp in first}
    assert names == {"jit.trace", "jit.lower", "jit.compile"}
    ours = [sp for sp in first if "lambda" in sp.attrs["fun_name"]]
    assert {sp.name for sp in ours} == names
    assert all(t_call - 1e-3 <= sp.t_start <= sp.t_end <= tr.clock()
               for sp in ours)
    compiles = sum(sp.name == "jit.compile" for sp in first)
    assert reg.snapshot()["counters"]["jit.compiles"] == compiles
    assert trace._on_jax_duration not in \
        monitoring.get_event_duration_listeners()
    assert trace._JIT_LISTENER is None


def test_profiler_capture_holds_program_spans(tmp_path, made):
    """While a tracer is installed each span also opens a profiler
    annotation: a jax.profiler capture shows it as a host event.  A
    retroactive span is not bridged."""
    import jax
    trace.install(trace.Tracer())
    try:
        jax.profiler.start_trace(str(tmp_path / "prof"))
        with trace.span("serve.step", pos=0):
            with trace.span("serve.sync"):
                np.asarray(jax.numpy.ones(2))
        trace.record("recovery.detect", 0.0, 1.0)
        jax.profiler.stop_trace()
    finally:
        trace.uninstall()
    assert made["annotation"] == 2
    (path,) = (tmp_path / "prof").rglob("*.xplane.pb")
    pd = jax.profiler.ProfileData.from_file(str(path))
    host = {ev.name for plane in pd.planes
            if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events}
    assert {"serve.step", "serve.sync"} <= host
    assert "recovery.detect" not in host


def _smoke_trainer(run_dir, ckpt_every=2):
    import jax.numpy as jnp
    from repro.api import CheckpointOptions
    from repro.configs import get_smoke_config
    from repro.launch.mesh import make_mesh
    from repro.runtime.trainer import TrainConfig, Trainer
    from repro.sharding import get_policy
    tcfg = TrainConfig(batch_size=2, seq_len=16, total_steps=16, lr=5e-3,
                       warmup_steps=2, ckpt_every=ckpt_every,
                       ckpt=CheckpointOptions(mode="async"),
                       compute_dtype=jnp.float32, remat=False)
    return Trainer(get_smoke_config("qwen1.5-0.5b"), tcfg,
                   make_mesh((1,), ("data",)), get_policy("baseline"),
                   run_dir)


@pytest.fixture(scope="module")
def trained(tmp_path_factory):
    """A smoke trainer's traced run with periodic saves (every 2 steps),
    a straggler save at step 3 and a preemption save at step 5."""
    from types import SimpleNamespace
    run = str(tmp_path_factory.mktemp("trained") / "run")
    t = _smoke_trainer(run)
    t.straggler = SimpleNamespace(record=lambda dt: t.step == 3)
    tr, reg = trace.Tracer(), metrics.MetricsRegistry()
    trace.install(tr)
    metrics.install(reg)
    try:
        t.run_until(4)
        out = t.run_until(8, preempt=lambda: t.step == 5)
        t.session.wait_pending()
    finally:
        trace.uninstall()
        metrics.uninstall()
    assert out["preempted"] and out["step"] == 5
    return {"run": run, "spans": list(tr.spans),
            "counters": reg.snapshot()["counters"]}


def test_trainer_saves_carry_their_trigger(trained):
    """Periodic, straggler and preemption saves carry ``trigger`` on
    their dump spans, the async writer's included, and count under
    dump.trigger.<t>."""
    phases = ("dump.pause", "dump.capture", "dump.ext_state", "dump.write")
    by_step = {}
    for sp in trained["spans"]:
        if sp.name in phases:
            by_step.setdefault(sp.attrs["step"], set()).add(
                sp.attrs.get("trigger"))
    assert by_step == {2: {"periodic"}, 3: {"straggler"},
                       4: {"periodic"}, 5: {"signal"}}
    writes = [sp for sp in trained["spans"] if sp.name == "dump.write"]
    assert writes and all(sp.thread != threading.main_thread().name
                          for sp in writes)
    counts = {k: v for k, v in trained["counters"].items()
              if k.startswith("dump.trigger.")}
    assert counts == {"dump.trigger.periodic": 2,
                      "dump.trigger.straggler": 1,
                      "dump.trigger.signal": 1}


def test_engine_dump_without_trigger_counts_as_call(tmp_path):
    eng = SnapshotEngine(str(tmp_path / "run"))
    eng.attach(lambda: {"train_state": make_state(n=1)})
    with observed(str(tmp_path / "obs")) as plane:
        eng.checkpoint(1)
        with trace.context(trigger="periodic"):
            eng.checkpoint(2)
        counters = plane.registry.snapshot()["counters"]
    assert counters["dump.trigger.call"] == 1
    assert counters["dump.trigger.periodic"] == 1


def test_resumed_train_step_holds_one_sync(trained, made):
    """run_until(n + 1) after restore(): one train.step holding one
    train.sync; with no tracer the same loop makes no span."""
    from types import SimpleNamespace
    t = _smoke_trainer(trained["run"], ckpt_every=0)
    t.straggler = SimpleNamespace(record=lambda dt: False)   # no saves
    step = t.restore()
    t.run_until(step + 1)                       # tracing off
    assert made == {"span": 0, "annotation": 0}
    tr = trace.Tracer()
    trace.install(tr)
    try:
        t.run_until(step + 2)
    finally:
        trace.uninstall()
    steps = [sp for sp in tr.spans if sp.name == "train.step"]
    syncs = [sp for sp in tr.spans if sp.name == "train.sync"]
    assert [sp.attrs["step"] for sp in steps] == [step + 1]
    assert len(syncs) == 1 and syncs[0].parent_id == steps[0].span_id
    assert steps[0].t_start <= syncs[0].t_start <= syncs[0].t_end \
        <= steps[0].t_end
    # every span opened under the installed tracer was bridged
    assert made["annotation"] == sum(not sp.name.startswith("jit.")
                                     for sp in tr.spans)


def test_decode_loop_spans_each_token(tmp_path, made):
    """decode_until(pos + k) gives k serve.step spans, one per token;
    every serve.sync (a wait for fetched tokens) nests in a serve.step,
    and each step's ``ahead`` counts the earlier steps still in flight
    when it was dispatched; with no tracer the same loop makes no span."""
    import jax
    from repro.configs import get_smoke_config
    from repro.data import TokenPipeline
    from repro.launch.mesh import make_mesh
    from repro.runtime.server import _LAG, DecodeServer
    from repro.sharding import get_policy
    cfg = get_smoke_config("mamba2-2.7b")
    mesh = make_mesh((1,), ("data",))
    srv = DecodeServer(cfg, get_policy("baseline"), mesh,
                       str(tmp_path / "srv"), max_seq=32)
    srv.load(srv.model.init(jax.random.key(0)))
    srv.start(TokenPipeline(cfg, 2, 8, seed=3).next())
    srv.decode_until(srv.pos + 2)               # tracing off, warm
    assert made == {"span": 0, "annotation": 0}
    pos = srv.pos
    tr = trace.Tracer()
    trace.install(tr)
    try:
        srv.decode_until(pos + 5)
    finally:
        trace.uninstall()
    steps = [sp for sp in tr.spans if sp.name == "serve.step"]
    syncs = [sp for sp in tr.spans if sp.name == "serve.sync"]
    assert [sp.attrs["pos"] for sp in steps] == list(range(pos, pos + 5))
    step_ids = {sp.span_id for sp in steps}
    assert syncs and all(sp.parent_id in step_ids for sp in syncs)
    ahead = [sp.attrs["ahead"] for sp in steps]
    assert ahead[0] == 0 and all(0 < a <= _LAG for a in ahead[1:])
    # the drain at return runs inside the call's last step
    assert steps[-1].span_id in {sp.parent_id for sp in syncs}
    assert srv.tokens.shape[1] == 8 + 1 + 2 + 5


# ----------------------------------------------------- spans and nesting
def test_span_nesting_and_context():
    tr = trace.Tracer()
    trace.install(tr)
    try:
        with trace.context(job="j1"):
            with trace.span("dump.pause", step=3) as outer:
                with trace.span("dump.capture") as inner:
                    pass
        assert inner.parent_id == outer.span_id
        assert outer.parent_id is None
        assert outer.attrs["job"] == "j1"
        assert inner.attrs["job"] == "j1"
        # context restores on exit
        with trace.span("dump.write") as after:
            pass
        assert "job" not in after.attrs
        assert after.t_end >= after.t_start
    finally:
        trace.uninstall()


def test_span_error_attribution():
    tr = trace.Tracer()
    trace.install(tr)
    try:
        with pytest.raises(ValueError):
            with trace.span("dump.write"):
                raise ValueError("boom")
        assert tr.spans[-1].attrs["error"] == "ValueError"
    finally:
        trace.uninstall()


def test_schema_covers_every_emitted_span_name(tmp_path):
    """Every span name the codebase emits is a key in SPAN_SCHEMA (the
    docs table / class filter contract)."""
    import re
    import subprocess
    out = subprocess.run(
        ["grep", "-rhoE",
         r'(span|begin|record)\(\s*"[a-z_]+\.[a-z_]+"', "src/repro"],
        capture_output=True, text=True, cwd="/root/repo").stdout
    names = set(re.findall(r'"([a-z_]+\.[a-z_]+)"', out))
    assert names, "span-name grep found nothing (layout changed?)"
    missing = names - set(trace.SPAN_SCHEMA)
    assert not missing, f"spans missing from SPAN_SCHEMA: {missing}"


def test_pack_detail_spans_deterministic(tmp_path):
    """Detail mode under the pipelined pack writer: per-chunk spans are
    complete and deterministic across identical runs — same multiset of
    (name, chunk) whatever the thread interleaving, every span on a
    named worker thread, job context inherited from the constructor."""
    from repro.serialization.pack import PackWriterV2, open_pack

    state = make_state(n=3, kb=64)

    def one_run(base):
        base.parent.mkdir(parents=True, exist_ok=True)
        tr = trace.Tracer(detail=True)
        trace.install(tr)
        try:
            with trace.context(job="jpack"):
                w = PackWriterV2(str(base), stripes=2, workers=2,
                                 chunk_bytes=32 * 1024, compress=True)
                for k, v in state.items():
                    w.add(k, v)
                w.close()
        finally:
            trace.uninstall()
        return tr.spans

    spans_a = one_run(tmp_path / "a" / "p.pack")
    spans_b = one_run(tmp_path / "b" / "p.pack")

    def key(spans, name):
        return sorted((sp.name, sp.attrs.get("chunk"))
                      for sp in spans if sp.name == name)

    for name in ("pack.compress", "pack.append"):
        assert key(spans_a, name) == key(spans_b, name)
        assert key(spans_a, name), f"no {name} spans recorded"
    for sp in spans_a:
        if sp.name == "pack.compress":
            assert sp.thread.startswith("repro-pack-compress-")
            assert sp.attrs["job"] == "jpack"
        elif sp.name == "pack.append":
            assert sp.thread.startswith("repro-pack-stripe-")
    # the written pack is intact
    with open_pack(str(tmp_path / "a" / "p.pack")) as r:
        for name in r.names():
            r.verify_entry(name)


def test_pack_disabled_runs_no_detail_spans(tmp_path):
    """A non-detail tracer records phase spans but never per-chunk ones
    — the hot-loop guard keeps the pipeline out of the span stream."""
    from repro.serialization.pack import PackWriterV2
    tr = trace.Tracer(detail=False)
    trace.install(tr)
    try:
        w = PackWriterV2(str(tmp_path / "p.pack"), stripes=2, workers=2,
                         chunk_bytes=32 * 1024)
        for k, v in make_state(n=2, kb=32).items():
            w.add(k, v)
        w.flush()
        w.close()
    finally:
        trace.uninstall()
    names = {sp.name for sp in tr.spans}
    assert "pack.compress" not in names
    assert "pack.append" not in names
    assert "pack.flush" in names


# ------------------------------------------------------------ satellites
def test_replicator_stats_routed_and_warn_once(tmp_path):
    """Satellite: replica counters mirror into the registry; a
    replicator without ``last_stats`` warns exactly once instead of
    dropping its counters silently."""
    class NoStatsReplicator:
        def push(self, run_dir, step):
            return None

    state = make_state()
    eng = SnapshotEngine(str(tmp_path / "run"),
                         replicator=NoStatsReplicator())
    eng.attach(lambda: {"train_state": state})
    reg = metrics.MetricsRegistry()
    metrics.install(reg)
    try:
        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            eng.checkpoint(1)
            eng.checkpoint(2)
        hits = [x for x in w if "no last_stats" in str(x.message)]
        assert len(hits) == 1            # once, not per dump
        snap = reg.snapshot()
        assert snap["counters"]["replica.missing_stats"] == 2
        assert snap["counters"]["replica.push_count"] == 2
        assert snap["counters"]["dump.count"] == 2
    finally:
        metrics.uninstall()


def test_replicator_with_stats_mirrors_counters(tmp_path):
    from repro.core.replication import DirReplicator
    state = make_state()
    eng = SnapshotEngine(str(tmp_path / "run"),
                         replicator=DirReplicator(str(tmp_path / "peer")))
    eng.attach(lambda: {"train_state": state})
    reg = metrics.MetricsRegistry()
    metrics.install(reg)
    try:
        eng.checkpoint(1)
    finally:
        metrics.uninstall()
    snap = reg.snapshot()
    assert snap["counters"]["replica.push_count"] == 1
    assert "replica.missing_stats" not in snap["counters"]
    assert any(k.startswith("replica.") and k != "replica.push_count"
               for k in snap["counters"])
    # the historical stats keys are still published alongside
    assert any(k.startswith("replica_") for k in eng.last_stats)


def test_wait_pending_stall_is_observable(tmp_path, monkeypatch):
    """Satellite: a stalled async writer emits a span (stalled=True), a
    ``dump.pending_stall_s`` histogram sample, and a journal event —
    the raise is no longer the only trace it leaves."""
    from repro.api import CheckpointOptions
    state = make_state()
    eng = SnapshotEngine(str(tmp_path / "run"),
                         options=CheckpointOptions(mode="async"))
    eng.attach(lambda: {"train_state": state})
    release = threading.Event()
    orig_write = eng._write

    def slow_write(ctx):
        release.wait(5.0)
        return orig_write(ctx)

    monkeypatch.setattr(eng, "_write", slow_write)
    with observed(str(tmp_path / "obsrun")) as plane:
        eng.checkpoint(1)
        with pytest.raises(PendingWriteStalled):
            eng.wait_pending(timeout_s=0.05)
        release.set()
        eng.wait_pending()               # reap cleanly
        stalled = [sp for sp in plane.tracer.spans
                   if sp.name == "dump.wait_pending"
                   and sp.attrs.get("stalled")]
        assert len(stalled) == 1
        assert stalled[0].attrs["waited_s"] > 0
        hist = plane.registry.snapshot()["histograms"]
        assert hist["dump.pending_stall_s"]["count"] == 1
    events = export.load_journal(str(tmp_path / "obsrun"))
    stalls = [e for e in events if e.get("kind") == "pending_stall"]
    assert len(stalls) == 1 and stalls[0]["cls"] == "dump"


# ------------------------------------------------- journal and exporters
def _tiny_run(run_dir):
    """One synthetic observed run touching every event class."""
    with observed(str(run_dir)) as plane:
        with trace.context(job="j0"):
            with trace.span("dump.pause", step=1):
                time.sleep(0.001)
        t_mark = plane.tracer.clock()
        trace.record("recovery.detect", t_mark, t_mark + 0.01,
                     job="j0", cause="preemption")
        metrics.counter_add("dump.count")
        metrics.observe("dump.frozen_s", 0.25)
        metrics.gauge_set("pack.queue_depth", 2)
        journal.emit("fault", "host_kill", job="j0", at_step=3, t=0.1)
        journal.emit("job", "transition", job="j0", frm="running",
                     to="freezing", step=1)
        journal.emit("job", "transition", job="other", frm="pending",
                     to="running", step=0)
    return export.load_journal(str(run_dir))


def test_journal_validates_and_filters(tmp_path):
    events = _tiny_run(tmp_path / "run")
    assert export.validate_journal(events) == []
    assert events[0]["kind"] == "journal_open"
    # class filter
    faults = export.filter_events(events, cls="fault")
    assert [e["kind"] for e in faults] == ["host_kill"]
    # job filter crosses spans and plain events
    j0 = export.filter_events(events, job="j0")
    kinds = {(e.get("cls"), e.get("kind")) for e in j0}
    assert ("dump", "span") in kinds
    assert ("recovery", "span") in kinds
    assert ("fault", "host_kill") in kinds
    assert all(export._event_job(e) == "j0" for e in j0)
    # sorted by time
    ts = [export._event_t(e) for e in j0]
    assert ts == sorted(ts)


def test_chrome_trace_export(tmp_path):
    events = _tiny_run(tmp_path / "run")
    chrome = export.to_chrome_trace(events)
    blob = json.dumps(chrome)            # must be JSON-serializable
    parsed = json.loads(blob)
    evs = parsed["traceEvents"]
    complete = [e for e in evs if e["ph"] == "X"]
    instants = [e for e in evs if e["ph"] == "i"]
    meta = [e for e in evs if e["ph"] == "M"]
    assert {e["name"] for e in complete} >= {"dump.pause",
                                             "recovery.detect"}
    assert any(e["name"] == "fault:host_kill" for e in instants)
    assert any("->" in e["name"] for e in instants)
    assert any(e["name"] == "process_name" for e in meta)
    for e in complete:
        assert e["ts"] >= 0 and e["dur"] >= 0 and e["tid"] >= 1


def test_metrics_snapshot_flattens(tmp_path):
    events = _tiny_run(tmp_path / "run")
    flat = export.metrics_from_journal(events)
    assert flat["obs.counter.dump.count"] == 1
    assert flat["obs.gauge.pack.queue_depth"] == 2
    assert flat["obs.hist.dump.frozen_s.count"] == 1
    assert flat["obs.hist.dump.frozen_s.sum"] == pytest.approx(0.25)


def test_journal_tolerates_torn_tail(tmp_path):
    run = tmp_path / "run"
    _tiny_run(run)
    path = journal.journal_path(str(run))
    with open(path, "a") as f:
        f.write('{"v": 1, "cls": "dump", "ki')   # crash mid-line
    events = export.load_journal(str(run))
    assert export.validate_journal(events) == []


def test_journal_inherits_job_from_trace_context(tmp_path):
    with observed(str(tmp_path / "run")):
        with trace.context(job="jx"):
            journal.emit("dump", "commit", step=5)
        journal.emit("dump", "commit", step=6)
    events = export.load_journal(str(tmp_path / "run"))
    commits = {e["step"]: e.get("job")
               for e in events if e.get("kind") == "commit"}
    assert commits == {5: "jx", 6: None}


# ------------------------------------------------------ recovery + chaos
def test_recovery_phases_become_spans(tmp_path):
    from repro.orchestrator.recovery import RecoveryLog
    with observed(str(tmp_path / "run")) as plane:
        clk = plane.tracer.clock
        log = RecoveryLog(job_id="j9")
        t = clk()
        log.open("failure", t_interrupt=t, t_detect=t + 0.01,
                 step_at_interrupt=7, last_ckpt_step=6)
        log.mark_transfer(t + 0.01, t + 0.02, bytes_sent=10)
        log.mark_scheduled(t + 0.03)
        log.mark_restored(t + 0.05, restored_step=6)
        log.mark_materialized(t + 0.06)
        log.mark_caught_up(t + 0.08)
        names = [sp.name for sp in plane.tracer.spans]
        assert names == ["recovery.detect", "recovery.transfer",
                         "recovery.schedule", "recovery.restore",
                         "recovery.restore_background",
                         "recovery.replay"]
        assert all(sp.attrs["job"] == "j9" for sp in plane.tracer.spans)
        assert all(sp.t_end >= sp.t_start for sp in plane.tracer.spans)
    events = export.load_journal(str(tmp_path / "run"))
    kinds = [e["kind"] for e in events if e["cls"] == "recovery"]
    assert "incident_open" in kinds and "incident_closed" in kinds
    # persisted incident dicts unchanged by the span side-channel
    assert log.breakdown()[0]["total_s"] == pytest.approx(0.08, abs=1e-6)


def test_chaos_injections_land_in_journal(tmp_path):
    from repro.chaos.injector import FaultInjector
    from repro.chaos.plan import ChaosConfig, FaultEvent
    ev = FaultEvent(kind="host_kill", job_id="j1", at_step=4, seq=0)
    inj = FaultInjector(ChaosConfig(seed=0, hosts=1,
                                    counts={"host_kill": 1}, events=[ev]))
    with observed(str(tmp_path / "run")) as plane:
        inj._record(ev, step=4, host="host00")
        assert plane.registry.snapshot()["counters"][
            "chaos.injections"] == 1
    events = export.load_journal(str(tmp_path / "run"))
    faults = export.filter_events(events, cls="fault")
    assert len(faults) == 1
    assert faults[0]["kind"] == "host_kill"
    assert faults[0]["job"] == "j1"
    # audit trail untouched in shape (campaign fingerprints unaffected)
    assert inj.injections[0]["kind"] == "host_kill"


# --------------------------------------------------------------- the CLI
def test_cli_trace_events_metrics(tmp_path, capsys):
    from repro.cli import main
    run = tmp_path / "run"
    _tiny_run(run)

    assert main(["trace", str(run), "--chrome"]) == 0
    out = capsys.readouterr().out
    assert "trace.json" in out
    with open(run / "obs" / "trace.json") as f:
        chrome = json.load(f)
    assert any(e["ph"] == "X" for e in chrome["traceEvents"])

    assert main(["events", str(run), "--job", "j0",
                 "--class", "fault"]) == 0
    out = capsys.readouterr().out
    assert "host_kill" in out

    assert main(["metrics", str(run), "--json"]) == 0
    flat = json.loads(capsys.readouterr().out)
    assert flat["obs.counter.dump.count"] == 1

    # no journal -> clean error, not a stack trace
    assert main(["events", str(tmp_path / "nowhere")]) == 1
