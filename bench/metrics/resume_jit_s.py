"""JAX's jit phases per resume: the time covered by the program's
``jit.trace``, ``jit.lower`` and ``jit.compile`` spans (a compile or a
compile-cache load) begun in the window, over the resumes completed.

The spans are merged before they are summed: tracing a function traces
the jitted functions it calls, and each inner trace is a span of its
own inside the outer one."""
NAME = "resume_jit_s"
UNIT = "s"
LAYER = "runtime"
MOVES = "resume_s"
SOURCE = "program_span"
WORKLOADS = ["qwen05b.train_resume"]

PHASES = ("jit.trace", "jit.lower", "jit.compile")


def covered_s(intervals):
    """Seconds covered by the union of (t0, t1) intervals."""
    total, end = 0.0, float("-inf")
    for t0, t1 in sorted(intervals):
        if t1 > end:
            total += t1 - max(t0, end)
            end = t1
    return total


def read(run):
    spans = [(t0, t1) for name in PHASES
             for t0, t1, _ in run.window_spans(name)]
    resumes = run.window.work.get("resumes")
    if not spans or not resumes:
        return None
    return covered_s(spans) / resumes
