"""The decode step program's share of its roofline in the hybrid cell:
the least time of a step at the window's mean position
(``bench/position_flops.py``: every held bf16 weight, the recurrent
state and the key/value rows valid at that position, or the operations
at the bf16 peak) over the device time of one ``jit_decode_step`` run
in the trace."""
from bench import position_flops

NAME = "decode_step_roofline.hybrid"
UNIT = "%"
LAYER = "model step"
MOVES = "decode_tokens_s"
SOURCE = "device_trace"
WORKLOADS = ["granite4h.serve_longgen"]
PROGRAM = "jit_decode_step"


def read(run):
    if run.profile is None:
        return None
    seconds, runs = run.profile["modules"].get(PROGRAM, (0.0, 0.0))
    least = position_flops.decode_least_s(run)
    if not runs or not seconds or least is None:
        return None
    return 100.0 * least * runs / seconds
