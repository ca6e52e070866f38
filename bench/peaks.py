"""Published peaks per chip, keyed by ``jax.Device.device_kind``.

TPU v5e: Google Cloud documentation, "TPU v5e" (System architecture):
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s per chip.  JAX
names the chip "TPU v5 lite".  A device that is not in the table is an
error: a roofline against a guessed peak means nothing.
"""

from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class Peak:
    bf16_flops: float      # FLOP/s
    hbm_bytes: float       # bytes/s
    source: str


PEAKS = {
    "TPU v5 lite": Peak(197e12, 819e9, 'Google Cloud documentation, "TPU v5e"'),
}


def peak_for(device_kind: str) -> Peak:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peak for device kind {device_kind!r}; add its row "
            f"to bench/peaks.py with its source") from None
