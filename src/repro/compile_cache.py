"""JAX's persistent compilation cache, placed from outside or at a fixed path.

XLA compiles for the TPU take seconds to minutes per program, and the
op-by-op engine compiles one program per operator and row count.  Scripts
that drive the engine (``chip_smoke.py``, ``benchmarks/run.py``,
``benchmarks/trace_tpch.py``) call ``enable()`` once before their first
compile so that a second process finds those programs on disk.

- ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads the variable itself, and
  no directory is set in code.
- Otherwise the cache goes to ``<checkout>/.jax_cache``.  The path is
  fixed, never built from a temporary name, a pid or the time, so that
  the next process finds what this one wrote.

Either way every program is kept, however fast it compiled: JAX's
default keeps only those over a second, while a cold query on the TPU
spends most of its compile time in hundreds of programs of 0.05-0.5 s.

The test suite never calls this: tests compile with the in-memory cache
only.
"""
from __future__ import annotations

import os

ENV = "JAX_COMPILATION_CACHE_DIR"

#: ``<checkout>/.jax_cache`` (this file is ``<checkout>/src/repro/``).
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)


def enable() -> str:
    """Turn the persistent cache on; returns the directory it uses."""
    import jax

    path = os.environ.get(ENV)
    if not path:
        path = DEFAULT_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path
