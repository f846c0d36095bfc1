"""Process start-up helpers shared by the entry points: where the persistent
compilation cache lives, and which platform JAX is configured for.

Device selection itself is JAX's: ``JAX_PLATFORMS`` (or nothing, for
auto-detection) decides, and a run that cannot reach the configured backend
fails in ``jax.devices()``. Nothing here probes a backend, pins a platform or
falls back to another one.
"""

from __future__ import annotations

import os

#: the one knob for the cache location — JAX's own. When it is set, JAX reads
#: it at import and this module never overrides it.
CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"


def default_cache_dir() -> str:
    """``<checkout>/.jax_cache`` — a fixed path (the directory is part of the
    cache key, so one that moves never hits)."""
    return os.path.join(
        os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)))), ".jax_cache")


def enable_compile_cache(path: str | None = None) -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    With ``JAX_COMPILATION_CACHE_DIR`` set, that directory is the cache —
    ``jax_compilation_cache_dir`` is left exactly as JAX read it from the
    environment and ``path`` is ignored, so a value placed from outside the
    program always wins. Unset, a ``path`` argument places the cache; a call
    that names none keeps the directory already configured in this process
    (a replica's ``spec["cache_dir"]``, the test suite's own) and only
    otherwise uses :func:`default_cache_dir` — so every caller in one
    process agrees on one directory. Switching the cache off is JAX's own
    ``jax_enable_compilation_cache``. A directory that cannot be created
    raises: a cache that silently does nothing re-pays every compile.
    """
    import jax

    env = os.environ.get(CACHE_ENV, "").strip()
    if env:
        path = env
    else:
        path = (path or jax.config.jax_compilation_cache_dir
                or default_cache_dir())
        jax.config.update("jax_compilation_cache_dir", path)
    os.makedirs(path, exist_ok=True)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def effective_platforms() -> str:
    """The platform list JAX is configured with, without touching the
    backend: ``jax.config.jax_platforms`` (which a script may have written)
    else the ``JAX_PLATFORMS`` env var. Empty string when nothing is
    configured (JAX then auto-detects)."""
    import jax

    return (jax.config.jax_platforms or "").strip() or os.environ.get(
        "JAX_PLATFORMS", "").strip()


def effective_first_platform() -> str:
    """First entry of :func:`effective_platforms` (the backend JAX tries
    first); empty string when nothing is configured."""
    return effective_platforms().split(",")[0].strip()


def watchdog_stall_s(env_var: str, accel_default_s: float) -> float:
    """How long a device-touching loop may go silent before its
    :class:`~ddim_cold_tpu.utils.watchdog.StallWatchdog` acts.

    An explicit env value always wins (``0`` disarms; empty string counts as
    unset). Otherwise ``0`` (never armed) when the configured first platform
    is cpu — healthy CPU runs of heavy sections legitimately exceed any sane
    deadline — else ``accel_default_s``.
    """
    env = os.environ.get(env_var) or None
    if env is not None:
        return float(env)
    return 0.0 if effective_first_platform() == "cpu" else accel_default_s
