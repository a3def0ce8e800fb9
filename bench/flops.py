"""A step's least time on a chip, from its model operations and bytes.

Each model family counts its own in its reference module
(``bench/reference/<family>.py``: ``decode_flops``, ``decode_bytes``,
``param_count``) from the configuration file's published sizes: what the
algorithm needs, with no recomputation.  The peaks are
``bench/peaks.json``'s, by the chip's ``device_kind``.
"""
from __future__ import annotations

from typing import Any, Dict


def decode_least_s(ref, c: Dict[str, Any], batch: int,
                   peaks: Dict[str, Any]) -> float:
    """The least time a decode step could take on a chip with these
    peaks: its operations at the bf16 peak or its bytes at the memory
    bandwidth, whichever is longer."""
    return max(ref.decode_flops(c, batch) / peaks["bf16_flops"],
               ref.decode_bytes(c, batch) / peaks["hbm_bytes_per_s"])
