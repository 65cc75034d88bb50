"""jax's own account of its persistent compilation cache, for the tests
and test workers that assert a warm start.

Every XLA compile jax routes through the cache records
``compile_requests_use_cache``; one answered from the directory records
``cache_hits``; one that was compiled and then written records
``cache_misses``. With the write threshold at 0 s every compiled
program is written, so "zero fresh compiles" reads
``misses == 0 and hits == requests``.
"""

import contextlib
import json
import os
import subprocess
import sys

import jax
from jax.experimental.compilation_cache import compilation_cache

_EVENTS = {
    "/jax/compilation_cache/compile_requests_use_cache": "requests",
    "/jax/compilation_cache/cache_hits": "hits",
    "/jax/compilation_cache/cache_misses": "misses",
}


def child_env(cache_dir):
    """What a child process needs in its environment to read and write
    every compile at ``cache_dir`` (``jax_cache.configure`` leaves a
    placed cache alone)."""
    return {"JAX_COMPILATION_CACHE_DIR": str(cache_dir),
            "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "0"}


def run_worker(script, *args, cache_dir):
    """Run ``tests/<script>`` as a fresh process against the jax cache
    at ``cache_dir``; returns the JSON object of its last stdout line."""
    here = os.path.dirname(os.path.abspath(__file__))
    out = subprocess.run(
        [sys.executable, os.path.join(here, script), *args],
        capture_output=True, text=True, timeout=600,
        env={**os.environ, "PYTHONPATH": os.path.dirname(here),
             **child_env(cache_dir)})
    assert out.returncode == 0, out.stderr[-2000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


class CacheEvents:
    """Counts the three events from construction until ``close()``."""

    def __init__(self):
        self._counts = dict.fromkeys(_EVENTS.values(), 0)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event, **kwargs):
        name = _EVENTS.get(event)
        if name is not None:
            self._counts[name] += 1

    def snapshot(self):
        return dict(self._counts)

    def since(self, before):
        return {k: v - before[k] for k, v in self._counts.items()}

    def close(self):
        jax.monitoring.unregister_event_listener(self._on_event)


@contextlib.contextmanager
def placed_in_process(cache_dir):
    """Move THIS process's jax cache to ``cache_dir`` with the write
    threshold at 0 s, yield a ``CacheEvents``, and put the process's
    own placement back."""
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs")
    old = {k: getattr(jax.config, k) for k in keys}
    jax.config.update(keys[0], str(cache_dir))
    jax.config.update(keys[1], 0.0)
    compilation_cache.reset_cache()
    events = CacheEvents()
    try:
        yield events
    finally:
        events.close()
        for k, v in old.items():
            jax.config.update(k, v)
        compilation_cache.reset_cache()
