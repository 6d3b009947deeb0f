"""Published peaks of the devices this benchmark may run on, keyed by
``jax.devices()[0].device_kind``. A device that is not here is an
error, never a default: a roofline share against the wrong peak is a
wrong number under a right name.

Source: Google Cloud documentation, "TPU v5e" system architecture
page (cloud.google.com/tpu/docs/v5e): per chip 197 TFLOP/s bf16,
393 TOP/s int8, 16 GB HBM2e at 819 GB/s, 1,600 Gbit/s interchip
interconnect. The same figures are quoted in the on-chip-measurement
guide's section 4 and in PERF.md section 3 ("device").
"""

from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
    },
}


def peaks_for(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(
            f"no published peaks for device_kind={device_kind!r}; add a "
            f"sourced row to perfbench/harness/peaks.py"
        )
    return PEAKS[device_kind]
