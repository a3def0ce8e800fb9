"""The decode step program's share of its roofline: the least time a
step could take (bench/flops.py, with the model family's counts: bf16
weights and the recurrent state read and written once, or the
operations at the bf16 peak, whichever is longer) over the device time
of one ``decode_step`` run in the trace."""
from bench import flops

NAME = "decode_step_roofline"
UNIT = "%"
LAYER = "model step"
MOVES = "decode_tokens_s"
SOURCE = "device_trace"
WORKLOADS = ["mamba2.serve_snapshot"]
PROGRAM = "jit_decode_step"


def read(run):
    if run.profile is None or run.peaks is None:
        return None
    seconds, runs = run.profile["modules"].get(PROGRAM, (0.0, 0.0))
    if not runs or not seconds:
        return None
    least = flops.decode_least_s(
        run.cell.reference(), run.cell.config, run.cell.traffic["batch"],
        run.peaks)
    return 100.0 * least * runs / seconds
