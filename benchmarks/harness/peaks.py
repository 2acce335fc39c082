"""Published peaks of the chips the benchmark may run on, by the
`device_kind` JAX reports. A kind that is not here is an error, never a
default. Copied from bench.py:CHIP_PEAKS (see PERF.md, Open questions).

Source: Google Cloud documentation, "TPU v5e" system architecture:
197 TFLOP/s bf16, 16 GB HBM2e at 819 GB/s per chip.
"""
PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9,
                    "source": "Google Cloud documentation, TPU v5e"},
    "TPU v5e": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9,
                "hbm_bytes": 16e9,
                "source": "Google Cloud documentation, TPU v5e"},
}


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise SystemExit(
            f"benchmark: device kind {device_kind!r} is not in the peaks "
            f"table (benchmarks/harness/peaks.py); add it with its "
            f"source before measuring on it") from None
