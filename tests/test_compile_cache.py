"""Where the entry points put JAX's persistent compilation cache.

Each case runs in a child process: the cache settings are process-global
JAX config, and the child is held to the CPU platform.
"""

import os
import subprocess
import sys

_SCRIPT = r"""
import jax, jax.numpy as jnp
from repro.compile_cache import CACHE_DIR, enable_compile_cache
print("DIR", enable_compile_cache())
print("CONFIG", jax.config.jax_compilation_cache_dir)
print("DEFAULT", CACHE_DIR)
jax.jit(lambda x: x * 3 + 1)(jnp.arange(8)).block_until_ready()
"""

_IMPORT_ONLY = r"""
import jax
import repro.mapreduce, repro.core
print("CONFIG", jax.config.jax_compilation_cache_dir)
"""


def _run(script, tmp_path, **env):
    proc = subprocess.run(
        [sys.executable, "-c", script], capture_output=True, text=True,
        timeout=120, cwd=__file__.rsplit("/tests/", 1)[0],
        env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
             "HOME": str(tmp_path), "JAX_PLATFORMS": "cpu", **env},
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    return dict(line.split(" ", 1) for line in proc.stdout.splitlines())


def test_env_dir_is_left_to_jax_and_filled(tmp_path):
    cache = tmp_path / "cache"
    out = _run(_SCRIPT, tmp_path, JAX_COMPILATION_CACHE_DIR=str(cache))
    assert out["DIR"] == out["CONFIG"] == str(cache)
    assert os.listdir(cache)  # the compile above was written there


def test_unset_env_uses_repo_cache_dir(tmp_path):
    out = _run(_SCRIPT, tmp_path)
    assert out["DIR"] == out["CONFIG"] == out["DEFAULT"]
    assert out["DEFAULT"].endswith("/.jax_cache")
    assert os.path.isdir(out["DEFAULT"])


def test_importing_the_library_sets_no_cache(tmp_path):
    out = _run(_IMPORT_ONLY, tmp_path)
    assert out["CONFIG"] == "None"
