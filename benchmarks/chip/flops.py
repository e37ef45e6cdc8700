"""What every reference module's work counts share.

Each configuration's operations and bytes are counted by its reference
module (``references/<name>.py``, whose docstring states the contract:
a lower bound on the work the result needs). Weights are counted at
``BYTES_PER_PARAM`` and cache entries at ``KV_BYTES`` (bfloat16, as
served).
"""
from __future__ import annotations

from typing import Dict

BYTES_PER_PARAM = 2
KV_BYTES = 2


def least_time(ops: float, nbytes: float, peaks: Dict) -> float:
    """The least time the chip could take: the larger of the compute and
    the memory bound."""
    return max(ops / peaks["bf16_flops_per_s"],
               nbytes / peaks["hbm_bytes_per_s"])
