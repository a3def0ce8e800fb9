"""Background serialize-and-commit time per snapshot, from the engine's
``dump.write`` span around the pack pipeline."""
from bench import readers

NAME = "dump_write_s.serve"
UNIT = "s"
LAYER = "pack pipeline"
MOVES = "decode_tokens_s"
SOURCE = "program_span"
WORKLOADS = ["mamba2.serve_snapshot"]


def read(run):
    return readers.mean_span_s(run, "dump.write")
