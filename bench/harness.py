"""The general part of a benchmark run: find a cell's files by name,
check the chip, drive the cell's set-up, window and check, and print
the result line.

A cell is ``bench/workloads/<cell>.json``: it names a model
configuration (``bench/configs/<config>.json``) and a traffic mix
(``bench/traffic/<traffic>.json``).  The traffic mix names its driver
(``bench/drivers/<driver>.py``), and the configuration its model
family's plain reference (``bench/reference/<family>.py``, in the
interface of ``bench/judge.py``).  A per-layer metric is
``bench/metrics/<metric>.py``; it lists the cells it reads.  Adding any
of these is adding a file: nothing here names a cell, a configuration
or a model family.

A driver module says what it drives, ``KIND = "train"`` or ``"serve"``
(the calibration reads it), and has four functions:

    setup(cell) -> state           build, load, warm up; counted in setup_s
    window(state, cell) -> Window  measure for cell.seconds
    drain(state, cell)             let background work of the window end
    check(state, cell) -> [Check]  compare with the plain reference

``check`` runs after the window has closed and device memory has been
read, and frees the program's state before the reference runs.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import math
import pathlib
import shutil
import sys
import time
from typing import Any, Callable, Dict, List, Optional

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
RUNS = ROOT / "runs" / "bench"


class BenchError(RuntimeError):
    """The cell cannot run here: a missing file, no chip, too few chips."""


# ------------------------------------------------------------- the files
def load_json(kind: str, name: str) -> Dict[str, Any]:
    path = BENCH / kind / f"{name}.json"
    if not path.is_file():
        raise BenchError(f"no {kind} file {path.relative_to(ROOT)}")
    return json.loads(path.read_text())


def load_module(kind: str, name: str):
    path = BENCH / kind / f"{name}.py"
    if not path.is_file():
        raise BenchError(f"no {kind} module {path.relative_to(ROOT)}")
    spec = importlib.util.spec_from_file_location(
        f"bench.{kind}.{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reference(spec: Dict[str, Any]):
    """The plain reference of a configuration's model family."""
    return load_module("reference", spec["reference"])


def find_workload(name: str) -> Dict[str, Any]:
    """The workload file with its traffic mix and configuration."""
    w = load_json("workloads", name)
    return dict(w, name=name, traffic_spec=load_json("traffic", w["traffic"]),
                config_spec=load_json("configs", w["config"]))


def metric_readers(cell_name: str) -> List[Any]:
    """Every per-layer metric module that lists this cell."""
    mods = []
    for path in sorted((BENCH / "metrics").glob("*.py")):
        mod = load_module("metrics", path.stem)
        if cell_name in mod.WORKLOADS:
            mods.append(mod)
    return mods


def program_config(spec: Dict[str, Any]):
    """The program's ModelConfig with every mapped key set from the
    configuration file, so the program runs as the file states."""
    from repro.configs import get_config
    prog = spec["program"]
    cfg = get_config(prog["arch"])
    fields = {field: spec[key] for key, field in prog["fields"].items()}
    return dataclasses.replace(cfg, **fields)


# -------------------------------------------------------------- records
@dataclasses.dataclass
class Check:
    """One number compared with its limit; it passes at or under it."""
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return math.isfinite(self.value) and self.value <= self.limit


@dataclasses.dataclass
class Window:
    """What the timed window did: its end-to-end metrics, the work it
    completed, and its host-clock bounds (perf_counter seconds)."""
    metrics: Dict[str, float]
    units: Dict[str, str]
    attempted: int
    failed: int
    t0: float
    t1: float
    work: Dict[str, Any] = dataclasses.field(default_factory=dict)


@dataclasses.dataclass
class Cell:
    name: str
    workload: Dict[str, Any]
    traffic: Dict[str, Any]
    config: Dict[str, Any]
    seed: int
    seconds: float
    trace: bool
    run_dir: pathlib.Path
    spans: List[tuple] = dataclasses.field(default_factory=list)

    @property
    def chips(self) -> int:
        return int(self.workload["chips"])

    def program_config(self):
        return program_config(self.config)

    def reference(self):
        return reference(self.config)

    def span(self, name: str):
        """A benchmark-side span: kept always (cheap), and written into
        the profiler's trace when the run is traced."""
        return _BenchSpan(self, name)


class _BenchSpan:
    def __init__(self, cell: Cell, name: str):
        self.cell, self.name, self.ann = cell, name, None

    def __enter__(self):
        if self.cell.trace:
            import jax
            self.ann = jax.profiler.TraceAnnotation(self.name)
            self.ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter()
        if self.ann is not None:
            self.ann.__exit__(*exc)
        self.cell.spans.append((self.name, self.t0, t1, {}))
        return False


# ----------------------------------------------------------------- run
class _ProgramCounter:
    """Programs that JAX compiled or loaded from its compile cache while
    the counter was on: the window should need none."""
    COMPILE = "/jax/core/compile/backend_compile_duration"
    HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        import jax.monitoring
        self.on = False
        self.counts = {"compiled_or_loaded": 0, "cache_hits": 0}
        jax.monitoring.register_event_duration_secs_listener(self._took)
        jax.monitoring.register_event_listener(self._event)

    def _took(self, event, duration, **kw):
        if self.on and event == self.COMPILE:
            self.counts["compiled_or_loaded"] += 1

    def _event(self, event, **kw):
        if self.on and event == self.HIT:
            self.counts["cache_hits"] += 1

    def close(self):
        import jax.monitoring
        jax.monitoring.unregister_event_duration_listener(self._took)
        jax.monitoring.unregister_event_listener(self._event)


def _device_info(devices) -> Dict[str, Any]:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices),
            "memory_peak_bytes": max(peaks) if peaks else None}


def _peaks(kind: str) -> Dict[str, Any]:
    table = json.loads((BENCH / "peaks.json").read_text())
    if kind not in table:
        raise BenchError(f"device kind {kind!r} is not in bench/peaks.json")
    return table[kind]


def execute(args, *, t_start: float, require_tpu: bool = True,
            config_overrides: Optional[Dict[str, Any]] = None,
            traffic_overrides: Optional[Dict[str, Any]] = None,
            runs_root: pathlib.Path = RUNS,
            limit_overrides: Optional[Dict[str, float]] = None
            ) -> Dict[str, Any]:
    """One run of one cell; returns the result object.

    ``require_tpu=False``, the overrides and ``runs_root`` exist for the
    CPU tests alone: they drive the same path at a smoke size, where a
    number whose scale follows the widths needs a limit of its own.
    Such a result carries no device metric: its values are None."""
    w = find_workload(args.workload)
    traffic = dict(w["traffic_spec"], **(traffic_overrides or {}))
    driver = load_module("drivers", traffic["driver"])
    readers = metric_readers(args.workload) if args.trace else []

    import repro  # noqa: F401  the system under test; absent = no run
    import jax
    from repro.launch.compile_cache import use_compile_cache

    devices = jax.devices()
    on_chip = devices[0].platform == "tpu"
    if require_tpu and not on_chip:
        raise BenchError(f"JAX found no TPU (platform "
                         f"{devices[0].platform!r}); no CPU fallback")
    if len(devices) < int(w["chips"]):
        raise BenchError(f"cell needs {w['chips']} chips, JAX has "
                         f"{len(devices)}")
    devices = devices[:int(w["chips"])]
    peaks = _peaks(devices[0].device_kind) if on_chip else None
    use_compile_cache()
    # every program of the cell goes to the cache, so only a checkout's
    # first run compiles
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)

    run_dir = runs_root / args.workload
    shutil.rmtree(run_dir, ignore_errors=True)
    run_dir.mkdir(parents=True)
    cell = Cell(name=args.workload, workload=w, traffic=traffic,
                config=dict(w["config_spec"], **(config_overrides or {})),
                seed=args.seed, seconds=float(args.seconds),
                trace=bool(args.trace), run_dir=run_dir)
    tracer = profile = None
    programs = _ProgramCounter()
    try:
        cell.spans.append(("setup.init", t_start, time.perf_counter(), {}))
        state = driver.setup(cell)
        setup_s = time.perf_counter() - t_start
        if cell.trace:
            from repro.obs import trace as obs_trace
            tracer = obs_trace.Tracer()
            obs_trace.install(tracer)
            jax.profiler.start_trace(str(run_dir / "profile"))
        programs.on = True
        with cell.span("bench.window"):
            win = driver.window(state, cell)
        programs.on = False
        if cell.trace:
            jax.profiler.stop_trace()
        driver.drain(state, cell)
        timing = {"setup_s": setup_s, "window_s": win.t1 - win.t0}
        if tracer is not None:
            obs_trace.uninstall()
            t0 = time.perf_counter()
            profile = _reduce_profile(cell, tracer, len(devices))
            timing["trace_reduce_s"] = time.perf_counter() - t0
        device = _device_info(devices)
        gc.collect()
        t0 = time.perf_counter()
        checks = driver.check(state, cell)
        timing["check_s"] = time.perf_counter() - t0
        del state
    finally:
        programs.close()
        if tracer is not None:
            from repro.obs import trace as obs_trace
            obs_trace.uninstall()
        shutil.rmtree(run_dir, ignore_errors=True)
        gc.collect()

    limits = dict(w["limits"], **(limit_overrides or {}))
    for c in checks:
        if c.limit is None:
            c.limit = limits[c.name]
    correct = all(c.ok for c in checks)

    if cell.trace:
        view = RunView(cell=cell, window=win, profile=profile, peaks=peaks,
                       chips=len(devices), tracer=tracer)
        metrics = {}
        for mod in readers:
            value = mod.read(view) if on_chip else None
            if value is not None:
                metrics[mod.NAME] = {"value": value, "unit": mod.UNIT}
        if profile is not None:
            device.update(busy_s=profile["busy_s"],
                          window_s=profile["window_s"])
    else:
        metrics = {k: {"value": v, "unit": win.units[k]}
                   for k, v in win.metrics.items()}
        metrics["setup_s"] = {"value": setup_s, "unit": "s"}
    if not on_chip:                 # a CPU rehearsal measures no device
        metrics = {k: {"value": None, "unit": m["unit"]}
                   for k, m in metrics.items()}
    out = {"correct": correct, "attempted": win.attempted,
           "failed": win.failed, "metrics": metrics, "device": device}
    if cell.trace and profile is not None:
        out["breakdown"] = profile["breakdown"]
    out["work"] = win.work
    out["window_programs"] = programs.counts
    for name, t0, t1, _ in cell.spans:
        if name.startswith("setup."):
            timing[name] = timing.get(name, 0.0) + t1 - t0
    out["timing"] = timing if on_chip else {}
    out["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                     for c in checks}
    return out


def main(args, *, t_start: float) -> int:
    try:
        out = execute(args, t_start=t_start)
    except BenchError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    for name, c in out["checks"].items():
        verdict = "ok" if c["value"] <= c["limit"] else "FAIL"
        print(f"check {name} {c['value']!r} limit {c['limit']!r} {verdict}",
              file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0


# ------------------------------------------------------- per-layer view
@dataclasses.dataclass
class RunView:
    """What a per-layer metric reader may read."""
    cell: Cell
    window: Window
    profile: Optional[Dict[str, Any]]
    peaks: Optional[Dict[str, Any]]
    chips: int
    tracer: Any

    def spans(self, name: str) -> List[tuple]:
        """(t0, t1, attrs) of every finished span of this name, from the
        program's tracer and the benchmark's own spans."""
        out = [(t0, t1, a) for n, t0, t1, a in self.cell.spans if n == name]
        if self.tracer is not None:
            out += [(s.t_start, s.t_end, s.attrs) for s in self.tracer.spans
                    if s.name == name and s.t_end is not None]
        return sorted(out, key=lambda s: s[0])

    def window_spans(self, name: str) -> List[tuple]:
        """Spans of this name that started inside the timed window."""
        w = self.window
        return [s for s in self.spans(name) if w.t0 <= s[0] < w.t1]


def _reduce_profile(cell: Cell, tracer, n_devices: int):
    from bench import trace_reduce
    paths = sorted((cell.run_dir / "profile").rglob("*.xplane.pb"))
    if not paths:
        return None
    host_spans = list(cell.spans) + [
        (s.name, s.t_start, s.t_end, s.attrs) for s in tracer.spans
        if s.t_end is not None]
    window_t0 = next(t0 for n, t0, _, _ in cell.spans if n == "bench.window")
    return trace_reduce.summarize(str(paths[-1]), host_spans=host_spans,
                                  window_name="bench.window",
                                  window_t0=window_t0, n_devices=n_devices)
