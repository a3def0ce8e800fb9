"""The hybrid replica's frozen time per snapshot (weights, key/value
cache and recurrent state): quiesce, device-to-host capture and host
state, from the engine's ``dump.pause``, ``dump.capture`` and
``dump.ext_state`` spans."""
from bench import readers

NAME = "dump_frozen_ms.hybrid"
UNIT = "ms"
LAYER = "engine dump"
MOVES = "decode_tokens_s"
SOURCE = "program_span"
WORKLOADS = ["granite4h.serve_longgen"]


def read(run):
    s = readers.mean_per_step_s(
        run, ("dump.pause", "dump.capture", "dump.ext_state"))
    return None if s is None else 1000.0 * s
