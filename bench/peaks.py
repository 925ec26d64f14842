"""Published peaks of the chips the benchmark runs on, keyed by JAX's
``device_kind``. A kind that is not here is an error, never a default."""
from __future__ import annotations

SOURCE_V5E = ("Google Cloud documentation, 'TPU v5e' "
              "(cloud.google.com/tpu/docs/v5e): per chip")

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,       # FLOP/s
        "int8_ops": 393e12,         # OP/s
        "hbm_bytes_per_s": 819e9,   # bytes/s
        "hbm_bytes": 16e9,
        "source": SOURCE_V5E,
    },
}


def peaks(device_kind: str) -> dict:
    """The peak table row of ``device_kind``; KeyError for an unknown chip."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None


def roofline_seconds(flops: float, nbytes: float, device_kind: str) -> float:
    """Least time the chip needs for ``flops`` bf16 operations moving
    ``nbytes`` through HBM: the larger of the two bounds."""
    p = peaks(device_kind)
    return max(flops / p["bf16_flops"], nbytes / p["hbm_bytes_per_s"])
