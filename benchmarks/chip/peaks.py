"""Published peaks per accelerator, keyed by ``device.device_kind``.

A kind that is not in the table is an error: a share of a peak is never
computed against a guessed or default device.
"""
from __future__ import annotations

from typing import Dict

PEAKS: Dict[str, Dict] = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "int8_ops_per_s": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, 'TPU v5e' "
                  "(cloud.google.com/tpu/docs/v5e)",
    },
}


class UnknownDevice(KeyError):
    """The device kind has no row in ``PEAKS``."""


def peaks_for(device_kind: str) -> Dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise UnknownDevice(
            f"no published peaks for device kind {device_kind!r}; "
            f"known: {sorted(PEAKS)}") from None
