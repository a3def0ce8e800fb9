"""Share of the hybrid cell's decode window in which no operation ran
on the device, from the profiler's trace."""
from bench import readers

NAME = "device_idle.decode.hybrid"
UNIT = "%"
LAYER = "device"
MOVES = "decode_tokens_s"
SOURCE = "device_trace"
WORKLOADS = ["granite4h.serve_longgen"]


def read(run):
    return readers.idle_percent(run)
