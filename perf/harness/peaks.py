"""Published peaks, keyed by the EXACT ``device_kind`` jax reports.

Copied from ``bench.py:_PEAK_TFLOPS`` (which keeps the bf16 row only)
and extended with the HBM rows the byte-bound kernels need.  A device
that is not in the table is an error, not a default: add it with its
source.
"""

PEAKS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16 and
    # 819 GB/s of HBM bandwidth per chip, 16 GB of HBM
    "TPU v5 lite": {"bf16_flops_per_s": 197e12,
                    "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9},
}


def peaks(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise SystemExit(f"perf: no published peak for device_kind "
                         f"{device_kind!r}; add it to perf/harness/peaks.py "
                         "with its source")
    return PEAKS[device_kind]
