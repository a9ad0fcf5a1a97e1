"""The chip the run uses: presence, compile cache, compiles, memory."""

from __future__ import annotations

import os
from pathlib import Path

BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def check_devices(chips: int, require_tpu: bool = True):
    """The devices to run on; raises :class:`NoChip` instead of falling
    back to the CPU."""
    import jax
    devices = jax.devices()
    if require_tpu and devices[0].platform != "tpu":
        raise NoChip(f"JAX found no TPU (platform {devices[0].platform!r})")
    if len(devices) < chips:
        raise NoChip(f"{chips} chips asked for, {len(devices)} visible")
    return devices[:chips]


def enable_compile_cache(root: Path) -> str:
    """JAX's persistent compilation cache: ``$JAX_COMPILATION_CACHE_DIR``
    when set, else ``.jax_cache/`` in the checkout (a fixed path, since the
    path is part of what a later run must find).  Every compile is kept,
    however short."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        Path(root) / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileCounter:
    """Backend compilations and their seconds, as JAX reports them."""

    def __init__(self):
        import jax
        self.n = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **kwargs) -> None:
        if event == BACKEND_COMPILE_EVENT:
            self.n += 1
            self.seconds += duration


def peak_bytes(devices) -> int:
    """Peak device memory on the fullest chip (0 where the backend does
    not report it)."""
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks) if peaks else 0


def describe(devices) -> dict:
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices)}
