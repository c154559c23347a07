"""ONE cached accelerator-platform probe.

``jax.devices()[0].platform == "tpu"`` used to be copy-pasted across every
kernel wrapper and the fused round. Each call is (a) a backend-init trigger
— innocuous-looking module code could lock the device topology before
``launch.devices`` had a chance to configure it — and (b) a per-call device
query on hot paths. The probe below initializes the backend exactly once,
on first *use* (never at import), and caches the answer for the life of the
process; everything platform-conditional goes through it.

The cache is correct because a JAX process cannot change platform after
backend init — the first ``jax.devices()`` call pins it. Tests that fake a
platform can ``platform.cache_clear()``.
"""
from __future__ import annotations

import functools
import os
import pathlib

__all__ = ["platform", "on_tpu", "use_compile_cache"]

# Fixed in-checkout location of JAX's persistent compilation cache: the
# directory is part of the cache key, so it must not move between runs.
_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


@functools.lru_cache(maxsize=None)
def platform() -> str:
    """The default JAX backend's platform name ("cpu" / "gpu" / "tpu").

    First call initializes the JAX backend (by design: callers are already
    about to dispatch); later calls are a dict lookup.
    """
    import jax

    return jax.devices()[0].platform


def on_tpu() -> bool:
    """True when the default backend is a real TPU (the Pallas fast path)."""
    return platform() == "tpu"


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache for an entry point.

    When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and no
    directory is set here; otherwise the cache goes to ``.jax_cache`` at the
    repository root. Every program is cached: the scheduling-round programs
    compile in under a second each on a TPU, below JAX's default one-second
    threshold, yet a cold run compiles dozens of them. Entry points call
    this from ``main``; importing a module never does, so the test suite
    runs without the cache. Returns the directory in use.
    """
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(_CACHE_DIR))
    return str(_CACHE_DIR)
