"""JAX process set-up shared by tests, the benchmark and the driver
hooks: the cpu pin that gives tests a virtual multi-device mesh, and the
persistent compilation cache.

One installation is targeted (Python 3.12 / JAX 0.9.0 / libtpu 0.0.34).
JAX honours ``JAX_PLATFORMS`` on its own, so a process that only wants the
cpu backend needs the environment variable and nothing from here.
"""
from __future__ import annotations

import os
import re

_COUNT_FLAG = "--xla_force_host_platform_device_count"

# The one in-checkout cache location used when JAX_COMPILATION_CACHE_DIR is
# not set. Fixed on purpose: the directory is part of the cache key, so a
# path that moves (home, temp name, pid, time) never hits.
COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".jax_cache"
)


def pin_cpu(n_devices: int | None = None) -> None:
    """Pin jax to the cpu platform, optionally sized to ``n_devices``
    virtual host devices (the mesh tests and the multichip dry run).

    Must run before any jax backend is initialized: XLA reads the
    host-platform device count at first initialization only. When
    ``n_devices`` is given it (re)sets the count so a stale value from the
    environment cannot undersize the mesh.
    """
    import jax
    from jax._src import xla_bridge

    if xla_bridge.backends_are_initialized():
        raise RuntimeError(
            "pin_cpu() called after a JAX backend was initialized; the cpu "
            "pin and device-count flags cannot take effect. Call it before "
            "any jax.devices()/jit dispatch in the process."
        )
    if n_devices is not None:
        flags = os.environ.get("XLA_FLAGS", "")
        if _COUNT_FLAG in flags:
            flags = re.sub(rf"{_COUNT_FLAG}=\d+", f"{_COUNT_FLAG}={n_devices}", flags)
        else:
            flags = f"{flags} {_COUNT_FLAG}={n_devices}".strip()
        os.environ["XLA_FLAGS"] = flags
    os.environ["JAX_PLATFORMS"] = "cpu"
    jax.config.update("jax_platforms", "cpu")


def enable_compile_cache() -> str:
    """Enable JAX's persistent compilation cache for this process and
    return the directory it lives in.

    The engine's step kernels cost seconds (cpu) to tens of seconds (tpu)
    of XLA compile per distinct KernelConfig, and every fresh process pays
    it again from scratch; the on-disk cache makes the second process
    start warm. Entry points opt in — library code never mutates global
    jax config.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX already honours it and
    no directory is set in code; otherwise the cache lives at
    ``COMPILE_CACHE_DIR`` inside the checkout. Every compile is cached
    (no minimum compile time), so a warm process backend-compiles nothing
    an earlier one already compiled. A set-up failure raises.
    """
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = COMPILE_CACHE_DIR
        os.makedirs(path, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
