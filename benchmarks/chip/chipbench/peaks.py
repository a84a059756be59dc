"""Published peaks of each accelerator the benchmark may run on, keyed by
the ``device_kind`` JAX reports.  A kind that is not here is an error."""

from __future__ import annotations

#: Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GiB of HBM
#: at 819 GB/s per chip.  JAX reports the chip as "TPU v5 lite".
_V5E = {
    "bf16_flops_per_s": 197e12,
    "hbm_bytes_per_s": 819e9,
    "hbm_bytes": 16 * 2**30,
    "source": "Google Cloud documentation, TPU v5e",
}

PEAKS = {
    "TPU v5 lite": _V5E,
    "TPU v5e": _V5E,
}


def peaks_for(device_kind: str) -> dict:
    """The peak table row for ``device_kind``; raises ``KeyError`` for a
    device the table does not know."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}; "
            f"known: {sorted(PEAKS)}"
        ) from None
