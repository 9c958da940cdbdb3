"""Which device a run used, and that device's published peaks."""

from __future__ import annotations

# Google Cloud documentation, "TPU v5e" system architecture: per chip
# 197 TFLOP/s bf16, 16 GB HBM2e at 819 GB/s. Keyed by jax's device_kind.
PEAKS = {
    "TPU v5 lite": {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12},
}


def peaks_for(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}: add a row "
            f"to benchmark/lib/device.py PEAKS with its source"
        )
    return PEAKS[device_kind]


def device_stamp() -> dict:
    """platform / kind / count as JAX reports them (tpusim.obs.bench
    device_stamp, without its JAX_PLATFORMS escape: the caller decides
    what a non-TPU backend means)."""
    import jax

    devices = jax.devices()
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def memory_peaks() -> dict:
    """The allocator's two peaks on the fullest device, as
    `Device.memory_stats()` gives them; 0 where the backend does not
    report (XLA:CPU). On the v5e they are two pools: `peak_bytes_in_use`
    counts arrays (inputs, outputs, the packed fetch buffer) and
    `peak_bytes_reserved` what a running program reserves for itself,
    where a scan's carry lives. PERF.md section 3 has the readings."""
    import jax

    peaks = {"peak_bytes_in_use": 0, "peak_bytes_reserved": 0}
    for d in jax.devices():
        stats = d.memory_stats() or {}
        for key in peaks:
            peaks[key] = max(peaks[key], int(stats.get(key, 0)))
    return peaks


def memory_peak_bytes(peaks: dict) -> int:
    """Bytes the fullest device held at its peak, at least: the larger
    pool's peak. The two peaks need not coincide, so their sum could
    overstate; the larger one cannot."""
    return max(peaks.values())
