"""Device-to-host capture rate of the hybrid replica's snapshots: the
bytes the engine counts as captured (``dump.captured_bytes``, also the
``bytes`` attribute of each ``dump.capture`` span) over the time of the
``dump.capture`` spans begun in the window.  A program whose capture
span carries no byte count reads nothing."""
NAME = "capture_gbps.hybrid"
UNIT = "GB/s"
LAYER = "engine dump"
MOVES = "decode_tokens_s"
SOURCE = "program_counter"
WORKLOADS = ["granite4h.serve_longgen"]


def read(run):
    spans = [(t1 - t0, a["bytes"]) for t0, t1, a
             in run.window_spans("dump.capture") if "bytes" in a]
    seconds = sum(s for s, _ in spans)
    if not spans or seconds <= 0:
        return None
    return sum(b for _, b in spans) / seconds / 1e9
