"""Analytic-MFU dedup (bench_common.mfu): the one shared helper must
reproduce the mfu_best values of the last committed chip rows (round 5,
2026-07-31, one v5e; the rows ROADMAP quotes) from their own recorded
throughputs — the proof that collapsing the three hand-rolled copies
(bench.py, bench_family.py x2, bench_resnet.py) changed no numbers."""

import pytest

import bench
import bench_common
import bench_family

V5E = "TPU v5 lite"
# (throughput, mfu_best) as recorded in round 5
R05 = {
    "transformer": (282446.9, 0.4364),
    "se_resnext50": (1671.0, 0.2154),
    "bert_base": (148326.0, 0.5029),
}

STEPS = 30  # the shared window protocol (bench_common.run_windows)


def _window_seconds(units_per_step, value):
    """Recover the recorded best-window seconds from a throughput row:
    value = units_per_step * STEPS / best."""
    return units_per_step * STEPS / value


def test_mfu_helper_arithmetic():
    # 1 TFLOP/step x 10 steps in 2 s = 5 TFLOP/s over the 197 TFLOP/s
    # peak
    from paddle_tpu import roofline

    assert bench_common.mfu(1e12, 10, 2.0, device_kind=V5E) == pytest.approx(
        5e12 / roofline.V5E_PEAK_BF16)


def test_mfu_divides_by_the_peak_of_the_device_that_ran():
    """No device_kind: the peak is the attached device's own row (the
    CPU here), never the v5e's; an unlisted device raises."""
    from paddle_tpu import roofline

    assert bench_common.mfu(1e12, 10, 2.0) == pytest.approx(
        5e12 / roofline.DEVICE_PEAKS["cpu"][0])
    with pytest.raises(KeyError, match="no roofline peaks"):
        bench_common.mfu(1e12, 10, 2.0, device_kind="TPU v9 imaginary")


def test_reproduces_r05_transformer_row():
    """Headline row (bench.py's copy): tokens/sec + analytic flops ->
    the recorded mfu_best. Per-token flops are batch-independent, so
    the check holds whatever batch the OOM backoff settled on."""
    class Cfg:
        d_model, d_inner, n_layer, n_head = 512, 2048, 6, 8

    batch, seq = 64, 256
    flops = bench.analytic_flops_per_step(Cfg, batch, seq, seq)
    value, mfu_best = R05["transformer"]
    best = _window_seconds(batch * seq, value)
    assert bench_common.mfu(flops, STEPS, best,
                            device_kind=V5E) == pytest.approx(
        mfu_best, abs=2e-4)


def test_reproduces_r05_se_resnext_row():
    """bench_family's first copy: images/sec x per-image train flops."""
    value, mfu_best = R05["se_resnext50"]
    batch = 128
    train_flops = 3.0 * bench_family.se_resnext50_fwd_flops_per_image()
    best = _window_seconds(batch, value)
    assert bench_common.mfu(batch * train_flops, STEPS, best,
                            device_kind=V5E) == pytest.approx(
        mfu_best, abs=2e-4)


def test_reproduces_r05_bert_row():
    """bench_family's second copy: tokens/sec + per-step train flops."""
    from paddle_tpu.models import bert

    value, mfu_best = R05["bert_base"]
    batch, seq = 64, 128
    flops = bench_family.bert_train_flops_per_step(bert.base(), batch,
                                                   seq)
    best = _window_seconds(batch * seq, value)
    assert bench_common.mfu(flops, STEPS, best,
                            device_kind=V5E) == pytest.approx(
        mfu_best, abs=2e-4)
