"""Published peaks of each accelerator, keyed by JAX's ``device_kind``.

One table for every metric that divides by a peak. A kind that is not
in the table is an error: a share of an unknown peak is not a number.
"""
from __future__ import annotations

#: device_kind -> peaks of ONE chip, with the page they come from
PEAKS = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "int8_ops": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, 'TPU v5e' "
                  "(cloud.google.com/tpu/docs/v5e)",
    },
}


def peaks(device_kind: str) -> dict:
    """The peaks of one chip of ``device_kind``; KeyError when unknown."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device_kind "
                       f"{device_kind!r}; known: {sorted(PEAKS)}") from None
