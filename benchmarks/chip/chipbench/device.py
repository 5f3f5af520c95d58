"""The chip the benchmark runs on: its check, its peaks and its memory."""
from __future__ import annotations

import sys

# Published peaks of one chip, keyed by `device_kind` as JAX reports it.
PEAKS = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, 'TPU v5e': 197 TFLOP/s bf16, "
                  "16 GB HBM at 819 GB/s per chip",
    },
}


class NoChip(SystemExit):
    """Raised when the machine lacks the chips a cell asks for."""


def peaks(kind: str) -> dict:
    """The peak table's row for `kind`; an unknown device is an error."""
    try:
        return PEAKS[kind]
    except KeyError:
        raise KeyError(f"device_kind {kind!r} is not in the peak table "
                       f"({sorted(PEAKS)})") from None


def require_chips(chips: int):
    """Print the devices JAX found; refuse anything but `chips` TPUs or more.

    Returns (device info for the result line, the devices the cell uses)."""
    import jax

    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    print(f"device: platform={info['platform']} device_kind={info['kind']} "
          f"count={info['count']}", file=sys.stderr, flush=True)
    if info["platform"] != "tpu":
        raise NoChip(f"no TPU: JAX found {info['platform']} devices")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} TPUs, JAX found {len(devs)}")
    peaks(info["kind"])
    return info, devs[:chips]


def memory_peak(devs) -> int:
    """`peak_bytes_in_use` of the fullest device (0 where it is not reported)."""
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0)) for d in devs)
