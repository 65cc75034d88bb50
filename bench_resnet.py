"""Benchmark: ResNet-50 ImageNet-shape training throughput on one TPU chip.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"}.
vs_baseline = achieved model FLOPs utilization / 0.35 (the target:
>=35% MFU for ResNet-50 on v5e). Model definition:
paddle_tpu/models/resnet.py (reference: benchmark/fluid/models/resnet.py:171),
synthetic ImageNet input (reference: benchmark/fluid/imagenet_reader.py),
bf16 AMP convs, full train step (fwd + autodiff + momentum) in one XLA
computation.
"""

from __future__ import annotations

import json

from bench_common import (
    attach_metrics,
    compile_with_oom_backoff,
    configure_process,
    enable_bench_metrics,
    log,
    measured_mfu,
    mfu,
    run_windows,
)

BATCH = 128
SHAPE = (3, 224, 224)
CLASSES = 1000


def resnet50_fwd_flops_per_image() -> float:
    """Analytic conv+fc FLOPs (2*MACs) for ResNet-50 at 224x224 (~4.1e9,
    the standard figure). Computed from the architecture so the number is
    auditable rather than folklore."""
    total = 0.0

    def conv(hw, cin, cout, k, stride=1):
        nonlocal total
        out_hw = hw // stride
        total += 2.0 * out_hw * out_hw * cout * cin * k * k
        return out_hw

    hw = conv(224, 3, 64, 7, 2)     # conv1 -> 112
    hw //= 2                        # maxpool -> 56
    cin = 64
    for filters, blocks, first_stride in (
        (64, 3, 1), (128, 4, 2), (256, 6, 2), (512, 3, 2),
    ):
        for b in range(blocks):
            stride = first_stride if b == 0 else 1
            # bottleneck: 1x1 reduce, 3x3, 1x1 expand (+ projection on b==0)
            conv(hw, cin, filters, 1)
            new_hw = conv(hw, filters, filters, 3, stride)
            conv(new_hw, filters, filters * 4, 1)
            if b == 0:
                conv(hw, cin, filters * 4, 1, stride)
            hw = new_hw
            cin = filters * 4
    total += 2.0 * cin * CLASSES    # fc
    return total


def main():
    # metrics-only telemetry: the registry snapshot rides every BENCH
    # row's `metrics` field (PT_BENCH_METRICS=0 opts out)
    enable_bench_metrics()
    configure_process()
    import jax

    import paddle_tpu as fluid
    from paddle_tpu.dataset import imagenet
    from paddle_tpu.models import resnet

    main_prog, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main_prog, startup):
        model = resnet.get_model(data_shape=SHAPE, class_dim=CLASSES,
                                 depth=50)
        fluid.optimizer.Momentum(0.1, momentum=0.9).minimize(model["loss"])
    main_prog._amp = True  # bf16 convs/matmuls, f32 master weights

    def make_exe():
        e = fluid.Executor()
        e.run(startup)
        return e

    # total exhaustion raises AllBatchesOOM: the row fails, non-zero exit
    exe, batch = compile_with_oom_backoff(
        make_exe,
        lambda e, b: e.run(main_prog,
                           feed=next(iter(imagenet.batched(b, 1)())),
                           fetch_list=[model["loss"]]),
        BATCH, floor=8)

    feeds = [
        {k: jax.device_put(v) for k, v in fd.items()}
        for fd in imagenet.batched(batch, 4, seed=33)()
    ]
    # best-of-3 windows, one sync per window (bench_common.run_windows)
    steps = 30
    best, mean = run_windows(exe, main_prog, model["loss"], feeds, steps)

    images_per_sec = batch * steps / best
    images_per_sec_mean = batch * steps / mean
    train_flops = 3.0 * resnet50_fwd_flops_per_image()  # bwd ~= 2x fwd

    mfu_best = mfu(batch * train_flops, steps, best)
    log(f"images/sec={images_per_sec:.1f}, "
        f"train GFLOP/image={train_flops / 1e9:.2f}, MFU={mfu_best:.3f}")

    print(json.dumps(attach_metrics({
        "metric": "resnet50_train_images_per_sec",
        "value": round(images_per_sec, 1),
        "unit": "images/sec",
        "vs_baseline": round(mfu_best / 0.35, 3),
        "value_mean": round(images_per_sec_mean, 1),
        "mfu_best": round(mfu_best, 4),
        "mfu_mean": round(mfu(batch * train_flops, steps, mean), 4),
        "measured_mfu": measured_mfu(main_prog, best, steps),
    })))


if __name__ == "__main__":
    main()
