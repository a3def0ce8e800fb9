"""The whole decode step's share of the chip's peak in the hybrid cell:
the least time of a step at the window's mean position (the larger of
its operations over the bf16 peak and its bytes over the memory
bandwidth, ``bench/position_flops.py``) over the window's time per
step, host and snapshots included."""
from bench import position_flops

NAME = "mfu.decode.hybrid"
UNIT = "%"
LAYER = "model step"
MOVES = "decode_tokens_s"
SOURCE = "host_clock"
WORKLOADS = ["granite4h.serve_longgen"]


def read(run):
    steps = run.window.work.get("decode_steps")
    least = position_flops.decode_least_s(run)
    if not steps or least is None:
        return None
    return 100.0 * least * steps / (run.window.t1 - run.window.t0)
