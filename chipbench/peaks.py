"""Published peaks of the chips the benchmark runs on, keyed by the
``device_kind`` JAX reports. A device that is not here is an error: a
roofline share against a guessed peak would mean nothing."""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Peaks:
    bf16_flops: float        # FLOP/s
    hbm_bytes_per_s: float   # bytes/s
    hbm_bytes: float         # bytes of device memory
    ici_bytes_per_s: float   # bytes/s a chip, the published figure taken
    #                          as one-way (the generous reading)
    source: str


TABLE = {
    # 197 TFLOP/s bf16, 16 GB HBM at 819 GB/s, 1,600 Gbit/s of
    # inter-chip interconnect a chip.
    "TPU v5 lite": Peaks(bf16_flops=197e12, hbm_bytes_per_s=819e9,
                         hbm_bytes=16e9, ici_bytes_per_s=1600e9 / 8,
                         source='Google Cloud documentation, "TPU v5e"'),
}


def for_device(kind: str) -> Peaks:
    try:
        return TABLE[kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {kind!r}; "
                       f"known: {sorted(TABLE)}") from None
