"""The whole decode step's share of the chip's peak: the least time a
step could take (the larger of its operations over the bf16 peak and its
bytes over the memory bandwidth, bench/flops.py with the model family's
counts) over the window's time
per step, host and snapshots included."""
from bench import flops

NAME = "mfu.decode"
UNIT = "%"
LAYER = "model step"
MOVES = "decode_tokens_s"
SOURCE = "host_clock"
WORKLOADS = ["mamba2.serve_snapshot"]


def read(run):
    steps = run.window.work.get("decode_steps")
    if not steps or run.peaks is None:
        return None
    per_step = (run.window.t1 - run.window.t0) / steps
    least = flops.decode_least_s(
        run.cell.reference(), run.cell.config, run.cell.traffic["batch"],
        run.peaks)
    return 100.0 * least / per_step
