"""Published peaks of one chip, keyed by the `device_kind` jax reports.

Source: Google Cloud documentation, "TPU v5e" (system architecture page):
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB of HBM at 819 GB/s per chip.
The chip reports itself as "TPU v5 lite". A device that is not in the
table is an error, never a default: a roofline share against a guessed
peak is worse than none.
"""

_V5E = {"bf16_flops": 197e12, "int8_ops": 393e12, "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": 'Google Cloud documentation, "TPU v5e"'}

PEAKS = {"TPU v5 lite": _V5E, "TPU v5e": _V5E}


def peaks_for(device_kind):
    """The peaks of `device_kind`, or LookupError for a chip that is not
    in the table."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise LookupError(
            f"no published peaks on record for device_kind "
            f"{device_kind!r}; add it to perf/harness/peaks.py with its "
            f"source (known: {sorted(PEAKS)})") from None
