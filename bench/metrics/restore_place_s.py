"""Rebuilding every leaf and starting its host-to-device copy in an
eager restore, per resume: the engine's ``restore.place`` span inside
``restore.critical``.  The copies land later, under ``train.sync``."""
from bench import readers

NAME = "restore_place_s"
UNIT = "s"
LAYER = "engine restore"
MOVES = "resume_s"
SOURCE = "program_span"
WORKLOADS = ["qwen05b.train_resume"]


def read(run):
    return readers.mean_span_s(run, "restore.place")
