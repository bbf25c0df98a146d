"""Published peaks of the chips this benchmark may run on.

Source: Google Cloud documentation, "TPU v5e" (system architecture):
197 TFLOP/s bf16, 16 GB of HBM at 819 GB/s per chip. Keyed by the
`device_kind` JAX reports; a kind that is not here is an error, never a
default (copied from bench.py's PEAK_FLOPS / PEAK_HBM_BW, which a later
PR may delete)."""

PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9},
    "TPU v5e": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9,
                "hbm_bytes": 16e9},
}


def peak_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise SystemExit(
            f"benchmark: no published peak for device_kind "
            f"{device_kind!r}; known: {sorted(PEAKS)}") from None
