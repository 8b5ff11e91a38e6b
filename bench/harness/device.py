"""The chip check, the compile tally and the device's memory peak."""
from __future__ import annotations

import contextlib
import time

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class NoAccelerator(SystemExit):
    """Raised, with a non-zero exit status, when no TPU (or too few) is found."""


def require_tpu(chips: int):
    """The devices of the run; exits non-zero unless JAX sees ``chips`` TPUs."""
    import jax

    try:
        devices = jax.devices()
    except RuntimeError as e:  # no backend could be initialised
        raise NoAccelerator(f"bench: no accelerator: {e}") from e
    if devices[0].platform != "tpu":
        raise NoAccelerator(f"bench: no TPU, JAX found platform {devices[0].platform!r}")
    if len(devices) < chips:
        raise NoAccelerator(f"bench: the cell needs {chips} chips, found {len(devices)}")
    return devices[:chips]


def describe(devices) -> dict:
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind, "count": len(devices)}


def memory_peak_bytes(devices) -> int | None:
    """``peak_bytes_in_use`` of the fullest device, where the backend reports it."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devices]
    peaks = [p for p in peaks if p is not None]
    return int(max(peaks)) if peaks else None


class CompileTally:
    """Counts XLA compile requests and their seconds while entered.

    JAX reports the backend-compile event around every compile request,
    also one that the persistent compilation cache answers; those are
    counted apart as ``cache_loads`` so that ``compiles`` are the real
    compilations."""

    def __init__(self):
        self.requests = 0
        self.seconds = 0.0
        self.cache_loads = 0

    @property
    def compiles(self) -> int:
        return self.requests - self.cache_loads

    def _duration(self, event: str, duration_secs: float, **_) -> None:
        if event == _COMPILE_EVENT:
            self.requests += 1
            self.seconds += duration_secs

    def _event(self, event: str, **_) -> None:
        if event == _CACHE_HIT_EVENT:
            self.cache_loads += 1

    def __enter__(self) -> "CompileTally":
        import jax

        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)
        return self

    def __exit__(self, *exc) -> None:
        import jax

        jax.monitoring.unregister_event_duration_listener(self._duration)
        jax.monitoring.unregister_event_listener(self._event)

    def mark(self) -> tuple:
        return (self.compiles, self.cache_loads, self.seconds)

    def since(self, mark: tuple) -> dict:
        return {
            "compiles": self.compiles - mark[0],
            "cache_loads": self.cache_loads - mark[1],
            "compile_s": self.seconds - mark[2],
        }


@contextlib.contextmanager
def stopwatch(out: dict, key: str):
    """Adds the seconds of the block to ``out[key]``."""
    t0 = time.perf_counter()
    try:
        yield
    finally:
        out[key] = out.get(key, 0.0) + time.perf_counter() - t0
