"""Peak rates of the chips the engine prices against, keyed by device kind.

``MemoryModel`` admission and the KV tier's ``OffloadPolicy`` price against
the chip an engine actually runs on: ``chip_for`` looks its peaks up from the
device's ``device_kind`` and refuses a kind it has no table entry for. The
simulator and the analytic roofline model the v5e target and name
``TPU_V5E`` explicitly.
"""
from __future__ import annotations

import dataclasses
from typing import Dict


@dataclasses.dataclass(frozen=True)
class ChipSpec:
    name: str
    peak_flops_bf16: float      # FLOP/s
    hbm_bw: float               # bytes/s
    hbm_bytes: float            # bytes
    ici_bw: float               # bytes/s per link
    vmem_bytes: float = 128 * 2**20
    host_bw: float = 25e9       # bytes/s host<->device (PCIe/DMA)


# Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB HBM at
# 819 GB/s, 1,600 Gbit/s chip-to-chip interconnect (4 links).
TPU_V5E = ChipSpec(
    name="tpu-v5e",
    peak_flops_bf16=197e12,
    hbm_bw=819e9,
    hbm_bytes=16 * 2**30,
    ici_bw=50e9,
)

# Reduced-bandwidth variant for the paper's NVLink-vs-PCIe contrast (Fig 8):
# the analogue of "no NVLink" is a DCN-attached slice (~1/8 the ICI bw).
TPU_V5E_SLOW_LINKS = dataclasses.replace(TPU_V5E, name="tpu-v5e-dcn",
                                         ici_bw=6.25e9)

# jax.Device.device_kind -> peaks
CHIPS: Dict[str, ChipSpec] = {
    "TPU v5 lite": TPU_V5E,
}


def chip_for(platform: str, device_kind: str) -> ChipSpec:
    """Peaks of a device given its ``platform`` and ``device_kind``.

    A CPU device runs the test rehearsal of the v5e path (reduced preset,
    interpret-mode kernels) and prices as ``TPU_V5E``. Any other device must
    be in ``CHIPS``: no peak rate is assumed for a kind this table lacks.
    """
    if platform == "cpu":
        return TPU_V5E
    try:
        return CHIPS[device_kind]
    except KeyError:
        raise ValueError(
            f"no peak rates for device kind {device_kind!r} "
            f"(platform {platform!r}); add it to repro.runtime.hw.CHIPS "
            f"(known: {sorted(CHIPS)})") from None
