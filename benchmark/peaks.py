"""Published peaks of the chips the benchmark may run on, keyed by
``device_kind`` as JAX reports it.  A device that is not here is an
error, never a default.

Source: Google Cloud documentation, "TPU v5e" system architecture
(197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2e at 819 GB/s, 1,600 Gbit/s
chip-to-chip interconnect)."""

from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 393e12,
                    "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9,
                    "ici_bits_per_s": 1600e9},
}
PEAKS["TPU v5e"] = PEAKS["TPU v5 lite"]


def lookup(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind {device_kind!r}: "
                       f"add it to benchmark/peaks.py with its source "
                       f"(known: {sorted(PEAKS)})")
    return PEAKS[device_kind]


def roofline_floor_s(flops: float, nbytes: float, peaks: dict) -> tuple:
    """Least time the chip could take for ``flops`` bf16 operations and
    ``nbytes`` of HBM traffic, and which of the two bounds it."""
    t_flops = flops / peaks["bf16_flops"]
    t_bytes = nbytes / peaks["hbm_bytes_per_s"]
    return (t_flops, "compute") if t_flops >= t_bytes else (t_bytes, "memory")
