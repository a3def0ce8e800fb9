"""Share of the decode window in which no operation ran on the device,
from the profiler's trace."""
from bench import readers

NAME = "device_idle.decode"
UNIT = "%"
LAYER = "device"
MOVES = "decode_tokens_s"
SOURCE = "device_trace"
WORKLOADS = ["mamba2.serve_snapshot"]


def read(run):
    return readers.idle_percent(run)
