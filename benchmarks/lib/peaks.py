"""Published peaks of the chips the benchmark may run on, keyed by JAX's
``device_kind``. A device that is not here is an error, never a default."""

# Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 393 TOP/s int8,
# 16 GB of HBM at 819 GB/s per chip.
PEAKS = {
    "TPU v5 lite": {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9,
                    "source": 'Google Cloud documentation, "TPU v5e"'},
    "TPU v5e": {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9,
                "hbm_bytes": 16e9,
                "source": 'Google Cloud documentation, "TPU v5e"'},
}


def peaks_for(device_kind):
    """Longest key that prefixes ``device_kind``; a miss is an error."""
    hits = [k for k in PEAKS if device_kind.startswith(k)]
    if not hits:
        raise SystemExit(
            f"benchmark: no published peaks for device_kind={device_kind!r}; "
            f"known: {sorted(PEAKS)}")
    return PEAKS[max(hits, key=len)]
