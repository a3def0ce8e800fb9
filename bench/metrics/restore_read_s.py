"""Reading and decoding every entry of the image in an eager restore,
per resume: the engine's ``restore.read`` span inside
``restore.critical``."""
from bench import readers

NAME = "restore_read_s"
UNIT = "s"
LAYER = "engine restore"
MOVES = "resume_s"
SOURCE = "program_span"
WORKLOADS = ["qwen05b.train_resume"]


def read(run):
    return readers.mean_span_s(run, "restore.read")
