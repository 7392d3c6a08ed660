"""Published peaks of each accelerator the benchmark may run on, keyed by
``jax.Device.device_kind``.  A device that is not in the table is an error,
never a default."""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Peaks:
    bf16_flops: float        # FLOP/s, dense bf16 matmul
    hbm_bytes_s: float       # bytes/s, device memory bandwidth
    hbm_bytes: float         # bytes of device memory
    source: str


PEAKS = {
    "TPU v5 lite": Peaks(
        bf16_flops=197e12, hbm_bytes_s=819e9, hbm_bytes=16e9,
        source="Google Cloud documentation, 'TPU v5e': 197 TFLOP/s bf16, "
               "16 GB HBM at 819 GB/s per chip"),
}


def peaks_for(device_kind: str) -> Peaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None


def least_time(flops: float, nbytes: float, peaks: Peaks):
    """The least time the chip could take for ``flops`` and ``nbytes``, and
    which bound sets it ("compute" or "memory")."""
    t_flops, t_bytes = flops / peaks.bf16_flops, nbytes / peaks.hbm_bytes_s
    return (t_flops, "compute") if t_flops >= t_bytes else (t_bytes, "memory")
