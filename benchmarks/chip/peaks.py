"""Peak rates of the chips the benchmark measures on, keyed by device kind.

Copied from the program's ``runtime/hw.py`` so that no change to the system
under test can move the yardstick. Source: Google Cloud documentation,
"TPU v5e": 197 TFLOP/s in bf16, 393 TOP/s in int8, 16 GB of HBM at 819 GB/s.
"""
from __future__ import annotations

from typing import Dict

PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "int8_ops": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
    },
}


def peak(device_kind: str) -> Dict[str, float]:
    """The peaks of ``device_kind``; a kind not in the table is an error."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no peak rates for device kind {device_kind!r} "
                         f"(known: {sorted(PEAKS)})") from None
