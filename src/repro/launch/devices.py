"""Process-level setup shared by the drivers: which devices a run uses, and
where JAX keeps its persistent compilation cache.

Both are explicit. On the CPU the drivers emulate a multi-device host with
XLA's forced host-platform device count; on an accelerator they take the
backend's own devices. A driver that asks for more devices than the
backend has fails instead of running on fewer.
"""
from __future__ import annotations

import os
from pathlib import Path

# fixed, so that every run of this checkout finds what earlier runs cached
# (the cache key includes the directory)
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"
_FORCE_FLAG = "--xla_force_host_platform_device_count"


def pick_devices(n: int) -> list:
    """The first ``n`` devices of JAX's default backend. Unless
    ``JAX_PLATFORMS`` names only other platforms, XLA's CPU client is first
    asked for ``n`` host devices: the flag shapes nothing but that client,
    so an accelerator backend keeps its own devices, and a host without one
    emulates ``n``. It takes effect only if JAX has not initialized its
    backend yet (call this before any JAX operation), and only if
    ``XLA_FLAGS`` does not already force a count. Raises ``RuntimeError``
    when the backend has fewer than ``n`` devices."""
    platforms = [p for p in os.environ.get("JAX_PLATFORMS", "").split(",")
                 if p]
    if not platforms or "cpu" in platforms:
        flags = os.environ.get("XLA_FLAGS", "")
        if _FORCE_FLAG not in flags:
            os.environ["XLA_FLAGS"] = f"{flags} {_FORCE_FLAG}={n}".strip()
    import jax
    devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(
            f"--devices {n}: the {devices[0].platform} backend has "
            f"{len(devices)} device(s) ({devices[0].device_kind})")
    return devices[:n]


def describe(devices) -> dict:
    """What a result names its devices by: platform, kind and count."""
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": len(devices)}


def enable_compile_cache(path: str | None = None) -> str:
    """Turn on JAX's persistent compilation cache and return its directory:
    ``JAX_COMPILATION_CACHE_DIR`` when it is set (JAX reads it itself and
    no directory is set here), else ``path``, else ``DEFAULT_CACHE_DIR``.
    Thresholds drop to zero so every executable is kept, small ones
    included: a resize or a re-admission then finds its shape again across
    runs and processes."""
    import jax
    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache_dir:
        cache_dir = str(path or DEFAULT_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    os.makedirs(cache_dir, exist_ok=True)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return cache_dir
