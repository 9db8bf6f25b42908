"""Published peaks per device kind, as JAX names the kind.  A kind that
is not here is an error, never a default."""

PEAKS = {
    "TPU v5 lite": dict(
        bf16_flops=197e12, int8_ops=393e12, hbm_bytes_per_s=819e9,
        hbm_bytes=16e9, ici_bits_per_s=1600e9,
        source="Google Cloud documentation, 'TPU v5e': 197 TFLOP/s bf16, "
               "393 TOP/s int8, 16 GB HBM at 819 GB/s, 1,600 Gbit/s ICI"),
}


def peaks_for(kind: str) -> dict:
    if kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind {kind!r}")
    return PEAKS[kind]
