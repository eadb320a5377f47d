"""The least time a piece of work can take on a chip: its operations at the
peak rate or its bytes at the peak bandwidth, whichever is longer.

What a decode step or a request costs is the architecture's to count
(``archs/<arch>.py``: ``decode_step``, ``request_flops``), from the lengths
the traffic really had, not from compiler estimates.
"""

from __future__ import annotations


def least_time(flops: float, nbytes: float, peak: dict) -> float:
    return max(flops / peak["bf16_flops_per_s"],
               nbytes / peak["hbm_bytes_per_s"])
