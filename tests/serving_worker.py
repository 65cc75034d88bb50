"""Subprocess worker for the fresh-process warm-start test
(tests/test_warm_start.py): one fresh "serving replica" process that

1. serves a saved inference model through the Predictor surface
   (``close()`` releases its compiled entries), then
2. spins a tiny-transformer ServingEngine and decodes two requests
   through the prefill + single-token-decode program pair,

and prints ONE JSON line with jax's persistent-cache events and the
executor accounting the parent asserts on. The parent places the cache
in this process's environment. Run twice against the same directory,
the second (warm) replica must compile nothing — every XLA compile a
cache hit — and emit byte-identical tokens.

Determinism contract (same as tests/executor_worker.py): every program
built here must lower to the same HLO in every process.
"""

import json
import os
import sys

# A serving replica is a single-device process. Scrub the parent test
# session's virtual-8-device XLA flag (tests/conftest.py) BEFORE backend
# init: the multi-device CPU path is the environment's known
# glibc-heap-corruption territory (ROADMAP watch item) and has no
# business in this worker.
os.environ["XLA_FLAGS"] = " ".join(
    f for f in os.environ.get("XLA_FLAGS", "").split()
    if not f.startswith("--xla_force_host_platform_device_count"))

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402

import paddle_tpu as fluid  # noqa: E402
from paddle_tpu import flags, inference, monitor, serving  # noqa: E402
from paddle_tpu.models import transformer as T  # noqa: E402

from jax_cache_events import CacheEvents  # noqa: E402


def main():
    model_dir = sys.argv[1]
    events = CacheEvents()
    flags.set_flags({"telemetry": True})

    # --- the Predictor surface of the replica ---
    pred = inference.create_predictor(
        inference.Config(model_dir).disable_tpu().set_batch_buckets([4]))
    x = np.linspace(-1.0, 1.0, 4 * 16, dtype=np.float32).reshape(4, 16)
    (probs,) = pred.run([x])
    pred_entries = len(pred._exe._cache)
    pred.close()
    closed_entries = len(pred._exe._cache)

    # --- the continuous-batching engine of the replica ---
    cfg = T.TransformerConfig(
        src_vocab_size=37, trg_vocab_size=41, max_length=64, d_model=16,
        d_inner=32, n_head=2, n_layer=1, dropout=0.0,
        label_smooth_eps=0.0)
    scope = fluid.Scope()
    main_prog, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main_prog, startup):
        T.build(cfg, is_test=True)
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(scope):
        exe.run(startup)
    eng = serving.ServingEngine(cfg, scope, slots=2, src_len=8, max_len=8)
    r1 = eng.submit([5, 6, 7])
    r2 = eng.submit([9, 4])
    eng.run_until_idle()
    eng.close()

    print(json.dumps({
        "jax_cache": events.snapshot(),
        "exec_misses":
            monitor.counter("pt_executor_cache_misses_total").value(),
        "outcomes": [r["cache"] for r in monitor.recent_steps()],
        "pred_entries": pred_entries,
        "closed_entries": closed_entries,
        "result": {
            "probs": [float(v).hex() for v in np.ravel(np.asarray(probs))],
            "tokens": [[int(t) for t in r1.tokens],
                       [int(t) for t in r2.tokens]],
        },
    }))


if __name__ == "__main__":
    main()
