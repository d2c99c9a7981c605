"""Persistent XLA compilation cache wiring.

The solver plane compiles one XLA program per (kernel, shape-bucket)
rung, and a cold process pays all of them before its first cycle.  The
JAX persistent compilation cache makes that one-time per machine:
compiled executables are serialized under a cache directory and
reloaded by any later process that finds the directory at the same
path (the path is part of the cache key's world: a directory that
moves never hits).

Reference analog: the Go scheduler has no compile step at all
(minimalkueue starts in milliseconds — test/performance/scheduler/
minimalkueue/main.go), so amortizing ours across restarts is part of
matching its operational profile (verdict r3 item 7).

One rule places the cache.  If ``JAX_COMPILATION_CACHE_DIR`` is set,
JAX already uses it: this module sets no directory in code.  If it is
not, the cache lives at ``DEFAULT_DIR``, a fixed path inside the
checkout (``.kueue-tpu/`` is git-ignored).
``KUEUE_TPU_COMPILE_CACHE=0`` turns the cache off, and a multi-device
CPU backend (a virtual mesh) never uses it: see ``enable``.
"""

from __future__ import annotations

import os

from .features import env_value

DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    ".kueue-tpu", "xla-cache")


def cache_dir() -> str | None:
    """The directory compiled programs live in, or None when the cache
    is disabled."""
    if env_value("KUEUE_TPU_COMPILE_CACHE") == "0":
        return None
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or DEFAULT_DIR


def enable() -> str | None:
    """Idempotently point JAX at the persistent compilation cache.

    Returns the cache directory, or None when the cache is off."""
    d = cache_dir()
    if d is None:
        return None
    import jax
    devices = jax.devices()
    if devices[0].platform == "cpu" and len(devices) > 1:
        # XLA:CPU (jaxlib 0.9.0) deadlocks when it runs a multi-device
        # executable it LOADED from the persistent cache: the devices
        # reach the program's collectives in different orders and
        # rendezvous.cc aborts the process after 40 s (freshly compiled,
        # the same program is fine).  A virtual CPU mesh is a test and
        # soak device, so it goes without the cache.  JAX decides once
        # per process whether it caches, at its first compile; solvers
        # are built before that.
        jax.config.update("jax_enable_compilation_cache", False)
        return None
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        os.makedirs(d, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", d)
    # cache every program, however small or quick to compile: the
    # solver's rungs are many small programs, a restart pays all of
    # them, and a warm process must find each one
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return d
