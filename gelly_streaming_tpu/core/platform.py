"""Backend selection and the persistent compile cache.

`use_cpu()` pins JAX to its CPU backend (tests, the CPU rehearsal of
the examples and `bench.py --cpu`); `cpu_mesh(n)` additionally asks
for n virtual CPU devices, the multi-chip test rig. Both must run
before JAX creates a backend. `enable_compile_cache()` places JAX's
persistent compilation cache for the entry points that run on the
chip (`chip_smoke.py`, `bench.py`, `examples/_bootstrap.py`).
"""

from __future__ import annotations

import os

# the cache directory when JAX_COMPILATION_CACHE_DIR is unset: fixed
# and inside the checkout, because the path is part of the cache key
CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def use_cpu() -> None:
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax

    jax.config.update("jax_platforms", "cpu")


def cpu_mesh(n_devices: int = 8) -> None:
    """Virtual n-device CPU platform (the multi-chip test rig)."""
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={n_devices}"
        ).strip()
    use_cpu()


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its
    directory. A JAX_COMPILATION_CACHE_DIR from the environment is
    JAX's own setting and is left alone; otherwise the cache goes to
    CACHE_DIR. Nothing else is configured."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if path:
        return path
    import jax

    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    return CACHE_DIR
