"""Published peaks of the chips the benchmark may run on, keyed by
`jax.devices()[0].device_kind`. A device that is not here is an error,
never a default: a share of a peak computed against the wrong chip is
worse than none.
"""

from __future__ import annotations

PEAKS = {
    # Google Cloud documentation, "TPU v5e": per chip
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "int8_ops_per_s": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "https://cloud.google.com/tpu/docs/v5e",
    },
}


class UnknownDevice(RuntimeError):
    pass


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise UnknownDevice(
            f"device_kind {device_kind!r} is not in benchmarks/harness/peaks.py "
            f"(known: {sorted(PEAKS)}); add its published peaks with their "
            f"source before measuring on it"
        ) from None
