"""Published peaks of the devices the benchmark may run on, keyed by
``device_kind`` as JAX reports it.  Copied from the program's
``telemetry/profiling.DEVICE_PEAKS`` (a later PR may change the program,
not the yardstick), with int8 and interconnect added from the same page.

Source: Google Cloud documentation, "TPU v5e" system architecture: one
chip has 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2e at 819 GB/s, and
1,600 Gbit/s of chip-to-chip interconnect.
"""

DEVICE_PEAKS = {
    "TPU v5 lite": {
        "hbm_bytes_per_s": 819e9,
        "bf16_flops_per_s": 197e12,
        "int8_ops_per_s": 393e12,
        "hbm_bytes": 16e9,
        "ici_bits_per_s": 1600e9,
        "source": "Google Cloud documentation, TPU v5e",
    },
}


def peaks_for(device_kind: str) -> dict:
    """The peaks of ``device_kind``; a kind the table lacks is an error,
    never a default."""
    try:
        return DEVICE_PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; add it to benchmark/peaks.py "
                       "with its source") from None
