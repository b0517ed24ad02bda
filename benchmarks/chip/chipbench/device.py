"""The chip a run measures, and the published peaks of each kind.

A measurement path that finds no TPU fails: it never falls back to the CPU,
whose timings say nothing about the chip.
"""

from __future__ import annotations

#: Published peaks by `jax.Device.device_kind`. A kind not listed here is an
#: error, not a default.
PEAKS = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "hbm_bytes_per_s": 819e9,
        "source": "Google Cloud documentation, 'TPU v5e' (per chip)",
    },
}


class NoChip(RuntimeError):
    """JAX runs on no accelerator of the kind a cell needs."""


def peak(kind: str) -> dict:
    if kind not in PEAKS:
        raise NoChip(f"no published peak for device kind {kind!r}; "
                     f"known: {sorted(PEAKS)}")
    return PEAKS[kind]


def require_tpu(chips: int):
    """The first of `chips` TPU devices JAX sees; `NoChip` otherwise."""
    import jax

    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu":
        raise NoChip(f"no TPU: JAX runs on {dev.platform} "
                     f"({dev.device_kind}, {len(devs)} device(s))")
    if len(devs) < chips:
        raise NoChip(f"cell wants {chips} chips, JAX sees {len(devs)} "
                     f"{dev.device_kind}")
    peak(dev.device_kind)
    return dev


def describe(dev, chips: int) -> dict:
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": chips}


def memory_peak_bytes(devs) -> int | None:
    """Peak bytes in use on the fullest of `devs`, where the backend
    reports it."""
    peaks = []
    for d in devs:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None
