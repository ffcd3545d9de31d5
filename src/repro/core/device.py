"""Process-level JAX settings shared by the entry points.

* The persistent compilation cache: ``JAX_COMPILATION_CACHE_DIR`` wins when
  it is set (JAX reads it itself); otherwise the cache lives at the fixed
  ``<checkout>/.jax_cache``.  The path is part of a cache entry's identity,
  so it never depends on a temp name, a PID or the time.
* One process per chip: a TPU belongs to the process that opened it, so a
  child that imports JAX cannot use it while the parent holds it.

Nothing here touches JAX on import; each entry point calls
:func:`enable_compile_cache` at the start of ``main``.
"""
from __future__ import annotations

import os
from pathlib import Path

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    Sets no directory when ``JAX_COMPILATION_CACHE_DIR`` is set, and
    :data:`DEFAULT_CACHE_DIR` otherwise.  Every compilation is cached,
    however short: a kernel compiles in about a second, under JAX's default
    threshold.
    """
    import jax

    cache = os.environ.get(CACHE_ENV)
    if not cache:
        cache = str(DEFAULT_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", cache)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return cache


def check_children_can_use_device(what: str, remedy: str) -> None:
    """Refuse to start child processes that need the accelerator this
    process already holds: the child would fail to open it or hang."""
    from jax._src import xla_bridge

    if not xla_bridge.backends_are_initialized():
        return
    import jax

    backend = jax.default_backend()
    if backend == "cpu":
        return
    raise RuntimeError(
        f"{what}: this process holds the {backend.upper()} "
        f"({jax.devices()[0].device_kind}), and a child process cannot open it "
        f"while the parent lives; {remedy}"
    )
