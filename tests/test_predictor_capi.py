"""Native predictor C API (csrc/predictor_capi.cc): a pure-C binary
loads an exported zoo model through the stable ABI and checks outputs
against the Python Predictor (reference: inference/api/api.cc +
paddle_fluid.map — the reference's native serving surface)."""

import os
import shutil
import subprocess

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import io, layers

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
CSRC = os.path.join(REPO, "csrc")
BIN = os.path.join(CSRC, "predictor_capi_test")


def test_c_api_serves_exported_model(tmp_path):
    if not (shutil.which("make") and shutil.which("g++")
            and shutil.which("cc") and shutil.which("python3-config")):
        pytest.skip("native toolchain unavailable")
    r = subprocess.run(["make", "-C", CSRC, "predictor_capi_test"],
                       capture_output=True, text=True)
    assert r.returncode == 0 and os.path.exists(BIN), r.stderr[-800:]

    # export a small MLP from the zoo path
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data("img", shape=[12], dtype="float32")
        h = layers.fc(x, 24, act="relu")
        logits = layers.fc(h, 5)
        prob = layers.softmax(logits)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        model_dir = str(tmp_path / "model")
        io.save_inference_model(model_dir, ["img"], [prob], exe,
                                main_program=main)

        # expected outputs from the Python Predictor
        from paddle_tpu.inference import Config, create_predictor

        batch = np.random.RandomState(0).randn(4, 12).astype(np.float32)
        pred = create_predictor(Config(model_dir))
        (expected,) = pred.run({"img": batch})
    expected = np.asarray(expected, np.float32)

    input_bin = str(tmp_path / "input.bin")
    expected_bin = str(tmp_path / "expected.bin")
    batch.tofile(input_bin)
    expected.tofile(expected_bin)

    env = {**os.environ, "PT_REPO": REPO, "JAX_PLATFORMS": "cpu"}
    out = subprocess.run(
        [BIN, model_dir, input_bin, "2", "4", "12", "img", expected_bin],
        capture_output=True, text=True, env=env, timeout=300)
    assert out.returncode == 0, (out.stdout + "\n" + out.stderr)[-1200:]
    assert "max_err" in out.stdout
