"""The control, the reference computed in float8 put in the program's
place, is judged not correct by each cell's limits (at the smoke size's
own limit where a number's scale follows the widths)."""
import json
import pathlib

import pytest

import smoke
from bench import calibrate, harness

ROOT = pathlib.Path(__file__).resolve().parents[2]
CELLS = [c["name"] for c in
         json.loads((ROOT / "BENCHMARK.json").read_text())["workloads"]]


@pytest.mark.parametrize("cell", CELLS)
def test_control_is_not_correct(cell, tmp_path):
    w = harness.find_workload(cell)
    config, traffic, smoke_limits = smoke.sizes(cell)
    got = calibrate.readings(
        cell, [5, 6, 7], ["control"], require_tpu=False, seconds=2.0,
        runs_root=tmp_path, config_overrides=config,
        traffic_overrides=traffic)
    limits = dict(w["limits"], **smoke_limits)
    for reading in got:
        assert any(reading[k] > lim for k, lim in limits.items()
                   if k in reading), reading
