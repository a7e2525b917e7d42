"""The device a run measures: the refusal of anything but a TPU, its
description for the result line, and JAX's own compile events."""
from __future__ import annotations


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def require_chips(chips: int) -> dict:
    """The device description of the result line; raises NoChip unless
    JAX's backend is a TPU with at least ``chips`` devices."""
    import jax

    devices = jax.devices()
    info = {"platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": len(devices)}
    if info["platform"] != "tpu" or info["count"] < chips:
        raise NoChip(f"needs {chips} TPU chip(s), JAX found {info}")
    return info


def memory_peak_bytes(chips: int) -> int | None:
    """Peak bytes in use on the fullest of the first ``chips`` devices,
    where the backend reports it."""
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.devices()[:chips]]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


class CompileClock:
    """Seconds XLA spent compiling, and how often, from JAX's own compile
    events. A persistent-cache hit compiles nothing and is not counted."""

    def __init__(self):
        import jax

        self.seconds, self.count = 0.0, 0

        def listen(event, duration, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.seconds += duration
                self.count += 1

        jax.monitoring.register_event_duration_secs_listener(listen)
