"""From a profiler trace to device busy time, top device operations and
idle time split by what the host was doing.

The JAX profiler writes ``<dir>/plugins/profile/<time>/<host>.xplane.pb``.
``jax.profiler.ProfileData`` reads it: planes, their lines, and events
with a start and a duration in nanoseconds on the trace's own clock.  A
device is a plane whose name starts with ``/device:`` and has a line of
XLA operations; the benchmark's window is the host event named after the
window's ``TraceAnnotation``.  Host spans (the program's ``repro.obs``
spans and the benchmark's own) are on ``time.perf_counter``: the window
annotation, opened at a known ``perf_counter`` reading, ties the clocks.
"""
from __future__ import annotations

import bisect
import heapq
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
TOP = 10


def union(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Merge overlapping [start, end) intervals; returns them sorted."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo: float, hi: float) -> List[Tuple[float, float]]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def gaps(busy: Sequence[Tuple[float, float]], lo: float, hi: float
         ) -> List[Tuple[float, float]]:
    """The idle intervals of [lo, hi) between merged busy intervals."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def _ops_lines(plane):
    lines = [ln for ln in plane.lines if ln.name == OPS_LINE]
    return lines or [ln for ln in plane.lines if "Ops" in ln.name]


def read(path: str):
    """The parsed trace of one .xplane.pb."""
    import jax
    return jax.profiler.ProfileData.from_file(path)


def op_label(module: str, op: str) -> str:
    """``jit_step/%fusion.3`` from a module event's name and an op's HLO
    text: the program without its fingerprint, the instruction's name."""
    return f"{module.split('(', 1)[0]}/{op.split(' = ', 1)[0].strip()}"


def window_ns(pd, name: str) -> Optional[Tuple[float, float]]:
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name == name:
                    return ev.start_ns, ev.start_ns + ev.duration_ns
    return None


def device_ops(pd) -> Dict[str, List[Tuple[float, float, str]]]:
    """Per device plane, its operations as (start_ns, end_ns, label),
    each labelled with the program (XLA module) it ran in."""
    out = {}
    for plane in pd.planes:
        if not plane.name.startswith("/device:"):
            continue
        mods = sorted((ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
                      for line in plane.lines if line.name == MODULES_LINE
                      for ev in line.events)
        starts = [m[0] for m in mods]
        evs = []
        for line in _ops_lines(plane):
            for ev in line.events:
                i = bisect.bisect_right(starts, ev.start_ns) - 1
                mod = mods[i][2] if i >= 0 and ev.start_ns < mods[i][1] \
                    else "no module"
                evs.append((ev.start_ns, ev.start_ns + ev.duration_ns,
                            op_label(mod, ev.name)))
        if evs:
            out[plane.name] = evs
    return out


def module_time(pd, lo: float, hi: float, n_devices: int
                ) -> Dict[str, List[float]]:
    """Per program (XLA module, fingerprint dropped): [seconds on the
    device inside [lo, hi), runs begun inside it], averaged over the
    devices used."""
    out: Dict[str, List[float]] = {}
    planes = sorted((p for p in pd.planes if p.name.startswith("/device:")
                     and any(ln.name == MODULES_LINE for ln in p.lines)),
                    key=lambda p: p.name)[:n_devices]
    for plane in planes:
        for line in plane.lines:
            if line.name != MODULES_LINE:
                continue
            for ev in line.events:
                s, e = ev.start_ns, ev.start_ns + ev.duration_ns
                c = min(e, hi) - max(s, lo)
                if c <= 0:
                    continue
                rec = out.setdefault(ev.name.split("(", 1)[0], [0.0, 0.0])
                rec[0] += c * 1e-9 / len(planes)
                rec[1] += (lo <= s) / len(planes)
    return out


def segments(host_spans: Sequence[tuple], skip: Tuple[str, ...]
             ) -> List[Tuple[float, float, str]]:
    """Host time cut at every span boundary, each piece labelled with
    the innermost span open in it (the one begun last): sorted
    ``(t0, t1, label)`` on ``perf_counter`` seconds, covering only time
    in which some span is open."""
    events = []
    for i, (name, t0, t1, _) in enumerate(host_spans):
        if name in skip or t1 is None or t1 <= t0:
            continue
        events += [(t0, 1, i, name), (t1, 0, i, name)]
    events.sort()
    out, heap, open_ = [], [], set()
    prev = None
    for t, starts, i, name in events:
        while heap and heap[0][1] not in open_:
            heapq.heappop(heap)
        if heap and prev is not None and t > prev:
            out.append((prev, t, heap[0][2]))
        if starts:
            open_.add(i)
            heapq.heappush(heap, (-host_spans[i][1], i, name))
        else:
            open_.discard(i)
        prev = t
    return out


def attribute(gap: Tuple[float, float], segs, starts: List[float],
              into: Dict[str, float]) -> None:
    """Add the gap's seconds to ``into`` by the label of each segment it
    overlaps; time no span covers goes to ``no span open``."""
    a, b = gap
    covered = 0.0
    i = max(bisect.bisect_right(starts, a) - 1, 0)
    while i < len(segs) and segs[i][0] < b:
        c = min(b, segs[i][1]) - max(a, segs[i][0])
        if c > 0:
            into[segs[i][2]] = into.get(segs[i][2], 0.0) + c
            covered += c
        i += 1
    if b - a > covered:
        into["no span open"] = into.get("no span open", 0.0) + b - a - covered


def summarize(path: str, *, host_spans: Sequence[tuple], window_name: str,
              window_t0: float, n_devices: int) -> Optional[Dict]:
    """Busy and window seconds, averaged over the devices used, and the
    breakdown: top device operations by time, idle time by host span.

    Returns None when the trace holds no window or no device operation:
    a reader then has nothing to read."""
    pd = read(path)
    win = window_ns(pd, window_name)
    per_dev = device_ops(pd)
    if win is None or not per_dev:
        return None
    lo, hi = win
    offset_s = lo * 1e-9 - window_t0        # trace clock minus perf_counter
    devs = sorted(per_dev)[:n_devices]
    segs = segments(host_spans, skip=(window_name,))
    starts = [g[0] for g in segs]
    busy_ns, op_ns, idle_s = [], {}, {}
    for dev in devs:
        evs = per_dev[dev]
        busy = union(clip([(s, e) for s, e, _ in evs], lo, hi))
        busy_ns.append(sum(e - s for s, e in busy))
        for s, e, name in evs:
            c = min(e, hi) - max(s, lo)
            if c > 0:
                op_ns[name] = op_ns.get(name, 0.0) + c / len(devs)
        for s, e in gaps(busy, lo, hi):
            attribute((s * 1e-9 - offset_s, e * 1e-9 - offset_s), segs,
                      starts, idle_s)
    busy_s = sum(busy_ns) / len(busy_ns) * 1e-9
    window_s = (hi - lo) * 1e-9
    top = sorted(op_ns.items(), key=lambda kv: -kv[1])[:TOP]
    idle = sorted(((k, v / len(devs)) for k, v in idle_s.items()),
                  key=lambda kv: -kv[1])[:TOP]
    return {"busy_s": busy_s, "window_s": window_s,
            "modules": module_time(pd, lo, hi, n_devices),
            "breakdown": {"device_ops": [[k, v * 1e-9] for k, v in top],
                          "idle_gaps": [[k, v] for k, v in idle]}}
