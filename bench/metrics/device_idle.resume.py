"""Share of the resume window in which no operation ran on the device,
from the profiler's trace."""
from bench import readers

NAME = "device_idle.resume"
UNIT = "%"
LAYER = "device"
MOVES = "resume_s"
SOURCE = "device_trace"
WORKLOADS = ["qwen05b.train_resume"]


def read(run):
    return readers.idle_percent(run)
