"""The harness finds every part of a cell by name, and the files agree
with BENCHMARK.json."""
import json
import os
import pathlib
import subprocess
import sys

import pytest

from bench import harness

ROOT = pathlib.Path(__file__).resolve().parents[2]
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())


def test_finds_a_workload_file_by_name():
    w = harness.find_workload("qwen05b.train_resume")
    assert w["config_spec"]["name"] == w["config"] == "qwen1.5-0.5b"
    assert w["traffic_spec"]["driver"] == "train_resume"
    assert harness.load_module("drivers", w["traffic_spec"]["driver"]).setup
    assert harness.reference(w["config_spec"]).hidden


def test_a_new_cell_is_new_files_alone(tmp_path, monkeypatch):
    """A cell, configuration, traffic mix and model family that no file
    of the harness names: found by name, sized and judged alike."""
    bench = tmp_path / "bench"
    for kind in ("workloads", "configs", "traffic", "drivers", "reference",
                 "metrics"):
        (bench / kind).mkdir(parents=True)
    cfg = json.loads((ROOT / "bench/configs/qwen1.5-0.5b.json").read_text())
    cfg.update(name="tiny-lm", reference="tiny_family")
    (bench / "configs" / "tiny-lm.json").write_text(json.dumps(cfg))
    (bench / "traffic" / "tiny_mix.json").write_text(json.dumps(
        {"driver": "tiny_driver", "batch": 2}))
    (bench / "workloads" / "tiny.cell.json").write_text(json.dumps(
        {"config": "tiny-lm", "traffic": "tiny_mix", "chips": 1,
         "why": "a test", "limits": {}}))
    (bench / "drivers" / "tiny_driver.py").write_text("def setup(c): pass\n")
    (bench / "reference" / "tiny_family.py").write_text(
        "def hidden(*a): pass\ndef logits(*a): pass\n")
    (bench / "metrics" / "tiny_metric.py").write_text(
        "NAME = 'tiny_metric'\nWORKLOADS = ['tiny.cell']\n")
    monkeypatch.setattr(harness, "BENCH", bench)
    monkeypatch.setattr(harness, "ROOT", tmp_path)
    w = harness.find_workload("tiny.cell")
    assert w["traffic_spec"]["batch"] == 2
    assert harness.load_module("drivers", "tiny_driver").setup
    assert harness.reference(w["config_spec"]).logits
    assert [m.NAME for m in harness.metric_readers("tiny.cell")] == [
        "tiny_metric"]


def test_unknown_workload_is_an_error():
    with pytest.raises(harness.BenchError):
        harness.find_workload("no.such_cell")


@pytest.mark.parametrize("cell", SPEC["workloads"], ids=lambda c: c["name"])
def test_cell_files_agree_with_benchmark_json(cell):
    w = harness.find_workload(cell["name"])
    assert (w["config"], w["traffic"], w["chips"], w["why"]) == (
        cell["config"], cell["traffic"], cell["chips"], cell["why"])
    harness.load_module("drivers", w["traffic_spec"]["driver"])


@pytest.mark.parametrize("metric", SPEC["per_layer"], ids=lambda m: m["name"])
def test_metric_reader_agrees_with_benchmark_json(metric):
    mod = harness.load_module("metrics", metric["name"])
    assert (mod.NAME, mod.UNIT, mod.LAYER, mod.MOVES, mod.SOURCE) == (
        metric["name"], metric["unit"], metric["layer"], metric["moves"],
        metric["source"])
    assert mod.WORKLOADS == metric["workloads"]
    assert [m.NAME for c in mod.WORKLOADS
            for m in harness.metric_readers(c) if m.NAME == mod.NAME]


@pytest.mark.parametrize("config", SPEC["configs"], ids=lambda c: c["name"])
def test_config_file_agrees_with_benchmark_json(config):
    spec = json.loads((ROOT / config["file"]).read_text())
    assert spec["name"] == config["name"]
    assert spec["source"] == config["source"]
    assert sorted(spec["reduced"]) == sorted(config["reduced"])
    harness.program_config(spec)          # every mapped field exists


def test_no_tpu_means_no_result(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_ENABLE_COMPILATION_CACHE="false")
    r = subprocess.run(
        [sys.executable, str(ROOT / "bench" / "run.py"), "--workload",
         "qwen05b.train_resume", "--seed", "1", "--seconds", "1"],
        capture_output=True, text=True, env=env, timeout=300)
    assert r.returncode != 0
    assert "{" not in r.stdout
    assert "no TPU" in r.stderr
