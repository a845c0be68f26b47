"""Where the entry points keep JAX's persistent compilation cache.

Called by the scripts a user runs (``chip_smoke.py``,
``python -m benchmarks.run``, ``python -m repro.launch.cluster``, the
examples) before their first compile, and never when the library is
imported: a process that only imports ``repro`` keeps JAX's own settings.

If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing
here changes it.  Otherwise the cache goes to the fixed path
``<repo root>/.jax_cache`` (gitignored): the path is part of the cache's
identity, so a directory that moved between runs would never hit.

Either way every compile is cached, however short: JAX's default skips
compiles under a second, and the served path's many small programs are
what the cache is for.
"""

from __future__ import annotations

import os
import pathlib

import jax

CACHE_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
