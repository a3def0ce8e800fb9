"""From ``restore()``'s return to the first resumed step's loss on the
host: trace, lower, load from the compile cache, run.  The benchmark's
own ``resume.first_step`` span."""
from bench import readers

NAME = "resume_first_step_s"
UNIT = "s"
LAYER = "runtime"
MOVES = "resume_s"
SOURCE = "host_clock"
WORKLOADS = ["qwen05b.train_resume"]


def read(run):
    return readers.mean_span_s(run, "resume.first_step")
