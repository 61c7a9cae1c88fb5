"""Published peaks of the chips the benchmark may run on, by exact
``device_kind``. A device that is not here is an error, not a default.

Source: Google Cloud documentation, "TPU v5e" system architecture: 197
TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2e at 819 GB/s per chip. (Copied
from ``deeplearning4j_tpu/runtime/attribution.DEVICE_PEAKS``; the benchmark
reads its own copy so that no later PR moves the yardstick.)
"""

DEVICE_PEAKS = {
    "TPU v5 lite": {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9},
}


def peaks_for(device_kind: str) -> dict:
    try:
        return DEVICE_PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}; add it to "
            "benchmarks/harness/peaks.py with its source") from None
