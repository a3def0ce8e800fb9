"""Smoke sizes for the CPU tests: the same cells at widths a CPU runs in
seconds.  A number a smoke run gives is never a device metric.

The sizes are the cell's own files': the ``smoke`` key of the
configuration file (published keys, shrunk) and of the traffic mix, and
the workload file's ``smoke_limits``.  A served token's logit gap scales
with the logits, which grow with the width and the depth, so a serving
cell's smoke size takes a limit of its own, set by the cell's rule from
its own readings; the training numbers are relative and keep the
cell's limits."""
import argparse
import pathlib
import tempfile
import time


def sizes(workload: str):
    """(config overrides, traffic overrides, limit overrides)."""
    from bench import harness
    w = harness.find_workload(workload)
    return (w["config_spec"]["smoke"], w["traffic_spec"]["smoke"],
            w.get("smoke_limits", {}))


def execute(workload: str, *, seed: int = 3, seconds: float = 2.0,
            trace: int = 0, **kw):
    from bench import harness
    config, traffic, limits = sizes(workload)
    args = argparse.Namespace(workload=workload, seed=seed, seconds=seconds,
                              trace=trace)
    with tempfile.TemporaryDirectory() as runs:
        return harness.execute(args, t_start=time.perf_counter(),
                               require_tpu=False,
                               config_overrides=config,
                               traffic_overrides=traffic,
                               runs_root=pathlib.Path(runs),
                               limit_overrides=limits, **kw)


def traffic_of(workload: str) -> str:
    from bench import harness
    return harness.find_workload(workload)["traffic"]
