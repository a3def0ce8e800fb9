"""The counters behind mfu.decode and decode_step_roofline: each model
family's reference counts the parameters the program's model has, for
each configuration the benchmark runs, and the decode step's bytes."""
import json
import math
import pathlib

import pytest

from bench import harness

ROOT = pathlib.Path(__file__).resolve().parents[2]
CONFIGS = [c["name"] for c in
           json.loads((ROOT / "BENCHMARK.json").read_text())["configs"]]


@pytest.mark.parametrize("name", CONFIGS)
def test_param_count_matches_the_program(name):
    from repro.models.encdec import build_model
    from repro.sharding import get_policy
    spec = harness.load_json("configs", name)
    model = build_model(harness.program_config(spec), get_policy("baseline"),
                        None)
    leaves = __import__("jax").tree.leaves(model.init_abstract())
    assert harness.reference(spec).param_count(spec) == sum(
        math.prod(x.shape) for x in leaves)


def test_decode_bytes_are_bf16_weights_and_f32_state():
    spec = harness.load_json("configs", "mamba2-2.7b")
    weights = 2 * (16 * (2560 * (2 * 5120 + 2 * 128 + 80) + 5120 * 2560
                         + 4 * (5120 + 256)) + 2560 * 50277)
    state = 16 * 8 * (5120 * 128 * 4 + 3 * (5120 + 256) * 2)
    assert harness.reference(spec).decode_bytes(spec, 8) == weights + 2 * state
