"""Subprocess worker for the warm fleet spin-up drill
(tests/test_fleet_serving.py): one fresh "fleet host" process that

1. starts a single-replica ServingFleet and serves two requests,
2. scales OUT by one replica (the autoscaler's spin-up path) and
   serves two more through the router,

and prints ONE JSON line with jax's persistent-cache events and the
token streams. The parent places the cache in this process's
environment (``JAX_COMPILATION_CACHE_DIR``, write threshold 0 s). The
in-process claim: the scaled-up replica shares the fleet's geometry, so
it lowers the same programs the first replica just compiled — the
spin-up adds ZERO cache misses even on a cold cache. Run the worker
twice against the same directory and the second (warm) process must
compile nothing — misses == 0 — with byte-identical tokens: the
cross-host warm-start contract fleet autoscaling rides.

Determinism contract (same as tests/serving_worker.py): every program
built here must lower to the same HLO in every process.
"""

import json
import os

# A serving fleet host is a single-device process. Scrub the parent
# test session's virtual-8-device XLA flag (tests/conftest.py) BEFORE
# backend init: the multi-device CPU path is the environment's known
# glibc-heap-corruption territory (ROADMAP watch item) and has no
# business in this worker.
os.environ["XLA_FLAGS"] = " ".join(
    f for f in os.environ.get("XLA_FLAGS", "").split()
    if not f.startswith("--xla_force_host_platform_device_count"))

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402

import paddle_tpu as fluid  # noqa: E402
from paddle_tpu import fleet_serving, flags  # noqa: E402
from paddle_tpu.models import transformer as T  # noqa: E402

from jax_cache_events import CacheEvents  # noqa: E402


def main():
    events = CacheEvents()
    flags.set_flags({"telemetry": True})

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

    fleet = fleet_serving.ServingFleet(
        cfg, scope, replicas=1, slots=2, src_len=8, max_len=10,
        poll_s=0.005)
    r1 = fleet.submit([5, 6, 7])
    r2 = fleet.submit([9, 4])
    cold_tokens = [r1.result(timeout=120), r2.result(timeout=120)]

    # the autoscaler's spin-up path: the new replica must read its
    # prefill + decode compiles from the cache the first replica
    # populated — zero NEW cache misses
    before = events.snapshot()
    fleet._spawn_replica()
    r3 = fleet.submit([5, 6, 7])
    r4 = fleet.submit([9, 4])
    scaled_tokens = [r3.result(timeout=120), r4.result(timeout=120)]
    spinup = events.since(before)
    replica_count = fleet.stats()["replica_count"]
    fleet.close()

    print(json.dumps({
        "jax_cache": events.snapshot(),
        "spinup": spinup,
        "replica_count": replica_count,
        "tokens": [[int(t) for t in s] for s in cold_tokens],
        "scaled_tokens": [[int(t) for t in s] for s in scaled_tokens],
    }))


if __name__ == "__main__":
    main()
