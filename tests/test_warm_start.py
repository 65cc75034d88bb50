"""Warm start of a fresh process: there is one answer to "where do
compiled programs live between processes", jax's persistent cache as
``jax_cache.configure()`` places it. A second process against the same
directory traces and lowers again, compiles nothing, and computes bit
for bit what the first computed."""

import pytest

import paddle_tpu as fluid
from paddle_tpu import io, layers

from jax_cache_events import run_worker


def _saved_model(tmp_path):
    """The inference model the serving replica's Predictor serves."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data("x", shape=[16], dtype="float32")
        probs = layers.softmax(layers.fc(x, 4))
    exe = fluid.Executor(fluid.CPUPlace())
    model_d = str(tmp_path / "model")
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        io.save_inference_model(model_d, ["x"], [probs], exe, main)
    return model_d


# case -> (worker script, its mode, fewest programs it compiles cold)
CASES = {
    "executor_run": ("executor_worker.py", "run", 2),
    "run_steps_window": ("executor_worker.py", "run_steps_window", 2),
    # eight virtual devices: the mesh under which a deserialised AOT
    # executable was refused ("expected 8 shards")
    "data_parallel": ("executor_worker.py", "data_parallel", 2),
    # predictor + startup + prefill + decode; its argument is the model
    "serving_replica": ("serving_worker.py", None, 4),
}


@pytest.mark.parametrize("case", list(CASES))
def test_fresh_process_warm_start(case, tmp_path):
    script, mode, n_programs = CASES[case]
    arg = mode if mode is not None else _saved_model(tmp_path)

    def launch():
        return run_worker(script, arg, cache_dir=tmp_path / "jax_cache")

    cold = launch()
    assert cold["jax_cache"]["hits"] == 0, cold
    assert cold["jax_cache"]["misses"] >= n_programs, cold
    assert cold["exec_misses"] >= n_programs

    warm = launch()
    # zero fresh compiles: everything asked of the compiler was read
    assert warm["jax_cache"]["misses"] == 0, warm
    assert warm["jax_cache"]["hits"] == warm["jax_cache"]["requests"]
    assert warm["jax_cache"]["hits"] == cold["jax_cache"]["misses"]
    # the executor's own cache is per process: it builds (traces) again
    assert warm["exec_misses"] == cold["exec_misses"]
    assert warm["outcomes"] == cold["outcomes"]
    assert set(warm["outcomes"]) <= {"hit", "miss"}
    assert warm["result"] == cold["result"]
    if case == "serving_replica":
        assert cold["pred_entries"] == 1 and cold["closed_entries"] == 0
