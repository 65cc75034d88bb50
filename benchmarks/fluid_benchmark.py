"""Model-zoo benchmark harness printing examples/sec.

The equivalent of the reference's benchmark driver
(reference: benchmark/fluid/fluid_benchmark.py:296-300 prints
``examples/sec`` for mnist / resnet / vgg / stacked_dynamic_lstm /
machine_translation), redesigned for this framework: every model runs as
one whole-program XLA computation; ``--parallel`` runs GSPMD data
parallelism over the visible devices (the reference's
``CompiledProgram.with_data_parallel`` path).

    python benchmarks/fluid_benchmark.py --model mnist --batch_size 128
    python benchmarks/fluid_benchmark.py --model resnet --iterations 30
    python benchmarks/fluid_benchmark.py --model machine_translation \
        --parallel

Models: mnist, resnet, se_resnext, vgg, stacked_dynamic_lstm (IMDB
sentiment), machine_translation (LSTM NMT seq2seq), transformer, bert,
deepfm.
"""

from __future__ import annotations

import argparse
import sys
import time

import numpy as np

sys.path.insert(0, __file__.rsplit("/", 2)[0])


def _synth(shape, dtype="float32", lo=0, hi=None, seed=0):
    r = np.random.RandomState(seed)
    if dtype == "int64":
        return r.randint(lo, hi, shape).astype(np.int64)
    return r.normal(0, 1, shape).astype(np.float32)


def build_model(name, args):
    """-> (feed_fn(step) -> dict, loss_var, examples_per_batch)"""
    import paddle_tpu as fluid

    b = args.batch_size
    if name == "mnist":
        from paddle_tpu.models import mnist

        model = mnist.get_model(batch_size=b)
        feeds = lambda s: {"pixel": _synth((b, 784), seed=s),
                           "label": _synth((b, 1), "int64", 0, 10, s)}
        return feeds, model["loss"], b
    if name in ("resnet", "vgg", "se_resnext"):
        from paddle_tpu.models import resnet, se_resnext, vgg

        mod = {"resnet": resnet, "vgg": vgg, "se_resnext": se_resnext}[name]
        model = mod.get_model(data_shape=(3, 224, 224), class_dim=1000)
        feeds = lambda s: {"data": _synth((b, 3, 224, 224), seed=s),
                           "label": _synth((b, 1), "int64", 0, 1000, s)}
        return feeds, model["loss"], b
    if name in ("stacked_dynamic_lstm", "stacked_lstm"):
        from paddle_tpu.models import stacked_lstm

        cfg = stacked_lstm.StackedLSTMConfig(max_len=args.seq_len)
        model = stacked_lstm.build(cfg)
        feeds = lambda s: stacked_lstm.make_batch(cfg, b, seed=s)
        return feeds, model["loss"], b
    if name == "machine_translation":
        from paddle_tpu.models import seq2seq

        cfg = seq2seq.Seq2SeqConfig()
        model = seq2seq.build(cfg)
        feeds = lambda s: seq2seq.make_batch(cfg, b, args.seq_len,
                                             args.seq_len, seed=s)
        return feeds, model["loss"], b
    if name == "transformer":
        from paddle_tpu.models import transformer as T

        cfg = T.TransformerConfig(src_vocab_size=10000, trg_vocab_size=10000,
                                  max_length=args.seq_len + 2)
        model = T.build(cfg)
        feeds = lambda s: T.make_batch(cfg, b, args.seq_len, args.seq_len,
                                       seed=s)
        return feeds, model["loss"], b
    if name == "bert":
        from paddle_tpu.models import bert

        cfg = bert.BertConfig()
        model = bert.build(cfg)
        feeds = lambda s: bert.make_batch(cfg, b, args.seq_len, seed=s)
        return feeds, model["loss"], b
    if name == "deepfm":
        from paddle_tpu.models import deepfm

        cfg = deepfm.DeepFMConfig()
        model = deepfm.build(cfg)
        feeds = lambda s: deepfm.make_batch(cfg, b, seed=s)
        return feeds, model["loss"], b
    raise SystemExit(f"unknown model '{name}'")


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--model", default="mnist")
    p.add_argument("--batch_size", type=int, default=32)
    p.add_argument("--iterations", type=int, default=30)
    p.add_argument("--skip_batch_num", type=int, default=5)
    p.add_argument("--seq_len", type=int, default=64)
    p.add_argument("--learning_rate", type=float, default=1e-3)
    p.add_argument("--parallel", action="store_true",
                   help="GSPMD data parallelism over visible devices")
    p.add_argument("--device", default=None, choices=[None, "cpu", "tpu"],
                   help="cpu forces the virtual host backend")
    p.add_argument("--amp", action="store_true", help="bf16 AMP")
    args = p.parse_args()

    import jax

    if args.device == "cpu":
        jax.config.update("jax_platforms", "cpu")
        jax.config.update("jax_num_cpu_devices", 8)
    from paddle_tpu import jax_cache

    jax_cache.configure()

    import paddle_tpu as fluid

    main_prog, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main_prog, startup):
        feed_fn, loss, examples = build_model(args.model, args)
        fluid.optimizer.Adam(args.learning_rate).minimize(loss)
    if args.amp:
        main_prog._amp = True

    exe = fluid.Executor()
    exe.run(startup)
    program = main_prog
    if args.parallel:
        program = fluid.CompiledProgram(main_prog).with_data_parallel(
            loss_name=loss.name)

    feeds = [{k: jax.device_put(v) for k, v in feed_fn(s).items()}
             for s in range(4)]
    t_compile = time.perf_counter()
    exe.run(program, feed=feeds[0], fetch_list=[loss])
    print(f"compile+first step: {time.perf_counter() - t_compile:.1f}s",
          file=sys.stderr)

    for i in range(args.skip_batch_num):
        exe.run(program, feed=feeds[i % 4], fetch_list=[loss])
    t0 = time.perf_counter()
    out = None
    for i in range(args.iterations):
        out = exe.run(program, feed=feeds[i % 4], fetch_list=[loss],
                      return_numpy=False)
    final_loss = float(np.asarray(out[0]))
    elapsed = time.perf_counter() - t0
    eps = examples * args.iterations / elapsed
    print(f"model={args.model} batch={args.batch_size} "
          f"iters={args.iterations} loss={final_loss:.4f}")
    print(f"{eps:.2f} examples/sec, {elapsed / args.iterations * 1000:.2f} "
          f"ms/step")


if __name__ == "__main__":
    main()
