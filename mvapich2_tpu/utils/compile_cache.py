"""Where compiled device programs are kept between processes.

Called from the device entry points (coll/device.bind_universes,
parallel/mesh.make_mesh, bench.main, chip_smoke) — never at import: a
host-only rank process must not touch jax.

The rule: when ``JAX_COMPILATION_CACHE_DIR`` is set, jax reads it by
itself and this module names no directory. When it is not, the cache
lives at ``<checkout>/.jax_cache`` — a fixed, git-ignored path (the
path is part of the cache key, so a directory that moves never hits).
Either way the minimum-compile-time threshold goes to 0: the kernels of
this library compile in about a second, just under jax's default
threshold, and are the programs worth keeping.

A process whose environment pins jax to the CPU backend gets no cache
placed: the cache exists to save chip time, and XLA:CPU re-loads its
cached executables with a page of machine-feature warnings per hit.
"""

from __future__ import annotations

import os

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DEFAULT_DIR = os.path.join(_CHECKOUT, ".jax_cache")


def ensure_compile_cache() -> str:
    """Point jax's persistent compilation cache at its directory (see
    the module docstring) and return that directory ("" when the
    environment asks for the CPU and nothing was placed). Idempotent."""
    from .detect import env_asks_for_cpu
    if env_asks_for_cpu():
        return os.environ.get("JAX_COMPILATION_CACHE_DIR", "")
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    if jax.config.jax_compilation_cache_dir != DEFAULT_DIR:
        jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR


def cache_entries(path: str) -> int:
    """Compiled programs currently in the cache directory ``path``
    (access-time side files are not entries)."""
    try:
        return sum(1 for f in os.listdir(path) if not f.endswith("-atime"))
    except FileNotFoundError:
        return 0
