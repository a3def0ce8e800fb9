#!/usr/bin/env python3
"""Run one benchmark cell once on the chip and print its result line.

    python bench/run.py --workload qwen05b.train_resume --seed 7 \
        --seconds 51 --trace 0

Everything the cell needs is found by name under ``bench/``: the
workload file names its model configuration and its traffic mix, and the
traffic mix names the driver that sets up, times and checks it.  The run
uses one process and every chip it was given; without a TPU, or with
fewer chips than the cell asks for, it exits non-zero and prints no
result.  The last line of standard output is one JSON object: with
``--trace 0`` the cell's end-to-end metrics, with ``--trace 1`` its
per-layer metrics read from spans, counters and the device trace.
"""
from __future__ import annotations

import argparse
import pathlib
import sys
import time

T_START = time.perf_counter()
BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent


def parse(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    for p in (ROOT / "src", ROOT):
        if str(p) not in sys.path:
            sys.path.insert(0, str(p))
    from bench import harness
    return harness.main(args, t_start=T_START)


if __name__ == "__main__":
    sys.exit(main())
