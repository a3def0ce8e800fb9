"""Time of ``restore()``'s critical path per resume: scan, read and
verify the image, place it on the device; the engine's
``restore.critical`` span."""
from bench import readers

NAME = "restore_critical_s"
UNIT = "s"
LAYER = "engine restore"
MOVES = "resume_s"
SOURCE = "program_span"
WORKLOADS = ["qwen05b.train_resume"]


def read(run):
    return readers.mean_span_s(run, "restore.critical")
