"""Benchmark helpers: one JSON line per metric (SURVEY §6 harness)."""

from __future__ import annotations

import json
import time
from typing import Callable, Optional

# Published peaks per chip, keyed by JAX's `device_kind`.  Source: Google
# Cloud documentation, "TPU v5e" (system architecture): 16 GB HBM2 at
# 819 GB/s, 197 TFLOP/s bf16.  A device that is not here is an error,
# never a default.
_V5E = {
    "hbm_bytes_per_s": 819e9,
    "bf16_flops_per_s": 197e12,
    "source": 'Google Cloud "TPU v5e" documentation',
}
PEAKS = {"TPU v5 lite": _V5E, "TPU v5e": _V5E}


def device_peaks(device_kind: Optional[str] = None) -> dict:
    """Peak table entry for `device_kind` (default: JAX's first device);
    raises KeyError for a device the table does not know."""
    if device_kind is None:
        import jax

        device_kind = jax.devices()[0].device_kind
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device_kind {device_kind!r}; add it "
            f"to benchmarks.util.PEAKS with its source"
        ) from None


def achieved_bw_frac(
    bytes_moved: Optional[float],
    wall_ms: Optional[float],
    device_kind: Optional[str] = None,
) -> Optional[float]:
    """Fraction of the device's peak HBM bandwidth achieved: bytes-moved
    / (wall x peak BW).  None when either input is missing/degenerate
    (e.g. a row that never timed); an unknown device raises."""
    if not bytes_moved or not wall_ms or wall_ms <= 0:
        return None
    peak = device_peaks(device_kind)["hbm_bytes_per_s"]
    return round(float(bytes_moved) / (wall_ms * 1e-3 * peak), 4)


def peak_bw_source(device_kind: Optional[str] = None) -> str:
    """Provenance of the peak achieved_bw_frac divides by."""
    return device_peaks(device_kind)["source"]


def measure_ms(fn: Callable[[], None], reps: int = 3, warmup: int = 1) -> float:
    for _ in range(warmup):
        fn()
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        times.append((time.perf_counter() - t0) * 1e3)
    times.sort()
    return times[len(times) // 2]


def emit(metric: str, value: float, unit: str = "ms", **extra) -> None:
    print(json.dumps({"metric": metric, "value": round(value, 3), "unit": unit, **extra}))
