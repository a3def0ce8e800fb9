"""Each driver end to end on the CPU at a smoke size: the result has the
contract's keys, the comparison passes, no number is written under a
device metric's name, and the window compiles nothing (a resume builds a
fresh trainer by design, as a restarted process would)."""
import json
import pathlib

import pytest

import smoke

ROOT = pathlib.Path(__file__).resolve().parents[2]
CELLS = [c["name"] for c in
         json.loads((ROOT / "BENCHMARK.json").read_text())["workloads"]]
REBUILDS = {"train_resume"}


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell", CELLS)
def test_cell_runs_end_to_end(cell, trace):
    out = smoke.execute(cell, trace=trace)
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(out)
    assert list(out)[-1] == "checks"
    assert out["correct"] is True, out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["device"]["platform"] == "cpu"
    assert all(m["value"] is None for m in out["metrics"].values())
    if not trace:
        assert "setup_s" in out["metrics"]
    if smoke.traffic_of(cell) not in REBUILDS:
        assert out["window_programs"]["compiled_or_loaded"] == 0
    json.dumps(out)
