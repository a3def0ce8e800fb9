"""The least time of a decode step whose work grows with its position
(a key/value cache read up to it): ``bench/flops.py``'s roofline at the
mean position of the window's steps, with the model family's counts
(``decode_flops`` and ``decode_bytes`` that take the position)."""
from __future__ import annotations

from typing import Optional


def mean_position(run) -> Optional[float]:
    """Mean position of the steps decoded in the window, from the
    driver's ``positions`` (first, one past the last)."""
    pos = run.window.work.get("positions")
    if not pos or pos[1] <= pos[0]:
        return None
    return (pos[0] + pos[1] - 1) / 2


def decode_least_s(run) -> Optional[float]:
    """Operations at the bf16 peak or bytes at the memory bandwidth,
    whichever is longer, for one step at the window's mean position."""
    at = mean_position(run)
    if at is None or run.peaks is None:
        return None
    ref, c = run.cell.reference(), run.cell.config
    batch = run.cell.traffic["batch"]
    return max(ref.decode_flops(c, batch, at) / run.peaks["bf16_flops"],
               ref.decode_bytes(c, batch, at)
               / run.peaks["hbm_bytes_per_s"])
