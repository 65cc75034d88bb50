"""Where compiled programs are cached between processes.

One rule, used by every entry point (perf/run.py, chip_smoke.py,
tests/conftest.py): where ``JAX_COMPILATION_CACHE_DIR`` is set, jax
already reads it and nothing is set in code, so whoever runs the program
places the cache; otherwise jax's persistent compilation cache lives in
ONE fixed directory inside the checkout. A directory that moves between
runs (a ``tempfile.mkdtemp`` name) never hits.
"""

from __future__ import annotations

import os
import shutil

_ROOT = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".cache")
JAX_CACHE_DIR = os.path.join(_ROOT, "jax")


def configure() -> str:
    """Place jax's persistent compilation cache; call before the first
    compile. Returns the directory in use."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    import jax

    jax.config.update("jax_compilation_cache_dir", JAX_CACHE_DIR)
    # >= 1 s: the CPU test suite's hundreds of sub-second compiles must
    # not bloat a tree that is copied to the chip machine
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)
    return JAX_CACHE_DIR


def fresh_dir(name: str) -> str:
    """A fixed, emptied directory under the checkout's cache root
    (chip_smoke.py's IR dumps)."""
    path = os.path.join(_ROOT, name)
    shutil.rmtree(path, ignore_errors=True)
    os.makedirs(path)
    return path
