"""Arithmetic the per-layer metric readers share.  Each reader returns
None where its run holds nothing to read, and the harness then leaves
the metric out of the line."""
from __future__ import annotations

from typing import Optional, Sequence


def mean_span_s(run, name: str) -> Optional[float]:
    """Mean duration of the spans of this name that began in the window."""
    spans = run.window_spans(name)
    if not spans:
        return None
    return sum(t1 - t0 for t0, t1, _ in spans) / len(spans)


def mean_per_step_s(run, names: Sequence[str]) -> Optional[float]:
    """Per save (spans grouped by their ``step``), the summed duration
    of these spans, averaged over the saves that began in the window."""
    per = {}
    for name in names:
        for t0, t1, attrs in run.window_spans(name):
            per[attrs.get("step")] = per.get(attrs.get("step"), 0.0) + t1 - t0
    return sum(per.values()) / len(per) if per else None


def idle_percent(run) -> Optional[float]:
    if run.profile is None:
        return None
    return 100.0 * (1.0 - run.profile["busy_s"] / run.profile["window_s"])
