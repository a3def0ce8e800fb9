"""CRC check of the image before ``restore()`` takes it, per resume:
the engine's ``restore.verify`` span inside ``restore.critical``."""
from bench import readers

NAME = "restore_verify_s"
UNIT = "s"
LAYER = "engine restore"
MOVES = "resume_s"
SOURCE = "program_span"
WORKLOADS = ["qwen05b.train_resume"]


def read(run):
    return readers.mean_span_s(run, "restore.verify")
