"""Published peaks of one chip, keyed by ``jax.Device.device_kind``.

Copied from the program's ``launch/hlo_analysis.DEVICE_PEAKS`` so that a
change to the program cannot move a roofline's denominator.
"""
SOURCE = 'Google Cloud documentation, "TPU v5e", per-chip specifications'

PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 393e12,
                    "hbm_bytes": 16e9, "hbm_bytes_per_s": 819e9},
}


def peaks(kind: str) -> dict:
    """Peaks of a device kind; a kind not in the table is an error."""
    try:
        return PEAKS[kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {kind!r}; add "
                       f"them to PEAKS with their source") from None
