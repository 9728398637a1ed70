"""Published peaks of one chip, keyed by ``jax.Device.device_kind``.

Source: Google Cloud documentation, "TPU v5e" (cloud.google.com/tpu/docs/v5e):
per chip 197 TFLOP/s bfloat16, 394 TOP/s int8, 16 GB of HBM at 819 GB/s.
A kind that is not in the table is an error, never a default.
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Peak:
    bf16_flops: float
    int8_ops: float
    hbm_bytes: float
    hbm_bw: float


PEAKS = {
    "TPU v5 lite": Peak(bf16_flops=197e12, int8_ops=394e12, hbm_bytes=16e9, hbm_bw=819e9),
}


def peak_for(kind: str) -> Peak:
    try:
        return PEAKS[kind]
    except KeyError:
        raise ValueError(f"no published peaks for device kind {kind!r}; "
                         f"known: {sorted(PEAKS)}") from None
