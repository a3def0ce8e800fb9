"""The host's own part of each decoded token: the server's
``serve.step`` spans (stage the last tokens, dispatch the step and the
argmax, fetch, append) less the ``serve.sync`` fetch inside each, over
the number of tokens decoded in the window."""
NAME = "decode_host_ms.serve"
UNIT = "ms"
LAYER = "runtime"
MOVES = "decode_tokens_s"
SOURCE = "program_span"
WORKLOADS = ["mamba2.serve_snapshot"]


def read(run):
    steps = run.window_spans("serve.step")
    if not steps:
        return None
    syncs = run.window_spans("serve.sync")
    host = (sum(t1 - t0 for t0, t1, _ in steps)
            - sum(t1 - t0 for t0, t1, _ in syncs))
    return 1e3 * host / len(steps)
