"""The first resumed step's wait for its loss on the host, per resume:
the trainer's ``train.sync`` span.  It holds the restored state's
host-to-device copies landing and the step itself."""
from bench import readers

NAME = "resume_wait_s"
UNIT = "s"
LAYER = "runtime"
MOVES = "resume_s"
SOURCE = "program_span"
WORKLOADS = ["qwen05b.train_resume"]


def read(run):
    return readers.mean_span_s(run, "train.sync")
