"""The device guard, what the result line says of the device, and the
compile meter.

A run measures the chip or nothing: with no TPU, or fewer chips than the
cell asks for, :func:`require_accelerator` raises and the run prints no
result.
"""
from __future__ import annotations


class NoAccelerator(RuntimeError):
    pass


def require_accelerator(chips: int) -> list:
    """The first ``chips`` TPU devices, or :class:`NoAccelerator`."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoAccelerator(f"no TPU: JAX found {devs[0].platform} devices; "
                            "the benchmark never falls back to the CPU")
    if len(devs) < chips:
        raise NoAccelerator(f"the cell asks for {chips} chips, JAX found "
                            f"{len(devs)}")
    return devs[:chips]


def describe(devices) -> dict:
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices)}


def memory_peak_bytes(devices) -> int:
    """``peak_bytes_in_use`` of the fullest device (0 where the backend
    keeps no such statistic, as the CPU does)."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks))


def memory_in_use_bytes(devices) -> int:
    """``bytes_in_use`` of the fullest device now (0 where the backend
    keeps no such statistic)."""
    return int(max((d.memory_stats() or {}).get("bytes_in_use", 0)
                   for d in devices))


class CompileMeter:
    """Backend compiles and their seconds, from JAX's monitoring events
    (the compile event spans persistent-cache reads too)."""

    def __init__(self):
        import jax

        self.compiles = 0
        self.seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.seconds += secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def snapshot(self) -> dict:
        return {"compiles": self.compiles, "compile_s": self.seconds,
                "cache_hits": self.cache_hits}
