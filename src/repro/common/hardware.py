"""Hardware model of the target platform (TPU v5e) and of the paper's platform.

All roofline math in :mod:`repro.core.roofline` and the DSE in
:mod:`repro.core.dse` reads these constants; a run on a chip looks its peaks
up by ``device_kind`` (:func:`chip_for_kind`).
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class ChipSpec:
    """Per-chip hardware constants."""

    name: str
    # Peak compute (FLOP/s).  int8 ops count as 2x bf16 on the v5e MXU.
    peak_flops_bf16: float
    peak_flops_int8: float
    # HBM
    hbm_bytes: int
    hbm_bw: float  # bytes/s
    # Inter-chip interconnect, per link.
    ici_bw_per_link: float  # bytes/s (one direction)
    ici_links: int  # usable links per chip in a 2D torus
    # On-chip memory (the analogue of the paper's LUT/URAM budget).
    vmem_bytes: int
    # Host <-> device (DCN for the pod axis)
    dcn_bw: float


# Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 394 TOP/s int8,
# 16 GB of HBM at 819 GB/s.
TPU_V5E = ChipSpec(
    name="tpu-v5e",
    peak_flops_bf16=197e12,
    peak_flops_int8=394e12,
    hbm_bytes=16 * 1024**3,
    hbm_bw=819e9,
    ici_bw_per_link=50e9,
    ici_links=4,
    vmem_bytes=128 * 1024**2,
    dcn_bw=25e9,
)

# The paper's platform, used by benchmarks/table1_comparison.py to reproduce
# the paper's own arithmetic (KV260: Zynq UltraScale+ XCK26, LPDDR4-2400 x32).
KV260_DDR_BW = 19.2e9  # bytes/s, theoretical LPDDR4 peak used in the paper's refs
KV260_POWER_W = 4.9  # PD-Swap's measured power (Table 1)

DEFAULT_CHIP = TPU_V5E

# ``jax.Device.device_kind`` -> the chip's peaks.
CHIPS_BY_KIND = {
    "TPU v5 lite": TPU_V5E,
}


def chip_for_kind(device_kind: str) -> ChipSpec:
    """The peaks of a device as JAX names it; an unknown kind is an error,
    never a default chip."""
    try:
        return CHIPS_BY_KIND[device_kind]
    except KeyError:
        raise ValueError(
            f"no peaks known for device kind {device_kind!r}; add it to "
            f"CHIPS_BY_KIND ({sorted(CHIPS_BY_KIND)})") from None


def mesh_chips(mesh_shape: tuple[int, ...]) -> int:
    n = 1
    for s in mesh_shape:
        n *= s
    return n
