"""Published peaks of the accelerators the benchmark runs on.

Keyed by ``jax.Device.device_kind``. A device that is not in the table
is an error, never a default. Nothing reads the peaks yet; a kernel's
roofline share (operations and bytes over these peaks, divided by its
device time) is the first reader.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "name": "TPU v5e",
        "bf16_flops_per_s": 197e12,
        "int8_ops_per_s": 393e12,
        "hbm_bytes": 16e9,
        "hbm_bytes_per_s": 819e9,
        "source": "Google Cloud documentation, 'TPU v5e' (system architecture)",
    },
}


def peaks(device_kind: str) -> dict:
    """The peak table entry of ``device_kind``; KeyError if unknown."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None


def describe(devices) -> dict:
    """The result line's ``device`` object for the devices a cell used:
    platform, kind and count as JAX reports them, and the peak bytes in
    use on the fullest of them."""
    kind = devices[0].device_kind
    peak = None
    for d in devices:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peak = max(peak or 0, int(stats["peak_bytes_in_use"]))
    return {"platform": devices[0].platform, "kind": kind,
            "count": len(devices), "memory_peak_bytes": peak}
