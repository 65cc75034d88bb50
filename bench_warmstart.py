"""Benchmark rider: cold vs warm start through the persistent compile
cache (compile_cache.py).

Launches the SAME child twice against one fresh ``compile_cache_dir``
(a fixed, emptied path in the checkout):
the first (cold) child traces + XLA-compiles the bench transformer and
publishes serialized executables; the second (warm) child is a fresh
process that must resolve every executor entry from disk — zero fresh
XLA compiles — and reach its first executed train step in a fraction of
the cold time.

Prints ONE JSON line in the driver format: ``value`` is the warm
compile+first-step wall seconds, ``vs_baseline`` is
``(0.10 * cold) / warm`` against the acceptance target "warm start
<= 10% of cold" (>1.0 beats the target). The cold seconds, the warm
child's hit/miss counters and its per-entry cache outcomes ride along
so the driver can verify the zero-fresh-compiles claim, not just the
wall time.

The parent never imports jax (a chip belongs to one process at a time;
each child holds it in turn). jax's OWN persistent compilation cache is
placed by ``jax_cache.configure`` like everywhere else: where it is warm
the cold child's seconds are not a cold XLA compile, so the row carries
``jax_cache_dir`` next to them.

Env knobs: ``PT_BENCH_BATCH``/``PT_BENCH_SEQ`` (bench.py's transformer
shape); ``JAX_PLATFORMS=cpu`` runs it on the CPU (fast smoke).
"""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
import time

BATCH = int(os.environ.get("PT_BENCH_BATCH", "64"))
SEQ = int(os.environ.get("PT_BENCH_SEQ", "256"))
VOCAB = 10000


def child(cache_dir: str):
    """One fresh process: build the bench transformer, run startup + one
    train step with the persistent cache at ``cache_dir``, print the
    compile+first-step wall seconds and the cache accounting."""
    import numpy as np

    import paddle_tpu as fluid
    from paddle_tpu import compile_cache, flags, jax_cache, monitor
    from paddle_tpu.models import transformer as T

    jax_cache_dir = jax_cache.configure()
    flags.set_flags({"telemetry": True, "compile_cache_dir": cache_dir})
    cfg = T.TransformerConfig(
        src_vocab_size=VOCAB,
        trg_vocab_size=VOCAB,
        max_length=SEQ + 2,
        d_model=512,
        d_inner=2048,
        n_head=8,
        n_layer=6,
        dropout=0.1,
    )
    main_prog, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main_prog, startup):
        model = T.build(cfg)
        fluid.optimizer.Adam(1e-4).minimize(model["loss"])
    main_prog._amp = True
    batch = T.make_batch(cfg, BATCH, SEQ, SEQ, seed=0)
    t0 = time.perf_counter()
    exe = fluid.Executor()
    exe.run(startup)
    out = exe.run(main_prog, feed=batch, fetch_list=[model["loss"]])
    loss = float(np.asarray(out[0]))  # forces the step to materialize
    dt = time.perf_counter() - t0
    print(json.dumps({
        "compile_first_step_s": dt,
        "loss": loss,
        "jax_cache_dir": jax_cache_dir,
        "stats": compile_cache.stats(),
        "outcomes": [r["cache"] for r in monitor.recent_steps()],
    }))


def _launch(cache_dir: str) -> dict:
    out = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--child", cache_dir],
        capture_output=True, text=True, timeout=1800)
    if out.returncode != 0:
        raise RuntimeError(
            f"warm-start child rc={out.returncode}, "
            f"stderr tail: {out.stderr[-1000:]}")
    return json.loads(out.stdout.strip().splitlines()[-1])


def main():
    # a fixed, emptied path under the checkout's cache root — what
    # paddle_tpu.jax_cache.fresh_dir gives, spelled out because importing
    # the package would import jax into this parent
    cache_dir = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                             ".cache", "bench_warmstart_cc")
    shutil.rmtree(cache_dir, ignore_errors=True)
    os.makedirs(cache_dir)
    cold = _launch(cache_dir)
    warm = _launch(cache_dir)
    cold_s, warm_s = cold["compile_first_step_s"], warm["compile_first_step_s"]
    print(json.dumps({
        "metric": "transformer_warm_start_compile_first_step_seconds",
        "value": round(warm_s, 3),
        "unit": "s",
        # target: warm <= 10% of cold; >1.0 beats it
        "vs_baseline": round((0.10 * cold_s) / warm_s, 3) if warm_s else 0.0,
        "cold_s": round(cold_s, 3),
        "jax_cache_dir": cold["jax_cache_dir"],
        "warm_hits": warm["stats"]["hits"],
        "warm_misses": warm["stats"]["misses"],
        "warm_errors": warm["stats"]["errors"],
        "warm_outcomes": warm["outcomes"],
    }))


if __name__ == "__main__":
    if len(sys.argv) > 1 and sys.argv[1] == "--child":
        child(sys.argv[2])
    else:
        main()
