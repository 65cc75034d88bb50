"""Benchmark: Transformer-base training throughput on one TPU chip, plus
the rider rows.

``python bench.py`` is a LAUNCHER. A chip belongs to the process that
first touches jax, so this parent never imports jax: it runs each row —
the headline included — as a child process in turn (``python bench.py
--row`` for the transformer rows, the other ``bench_*.py`` scripts for
the rest), merges their JSON rows, and exits non-zero when any child
failed. ``PT_BENCH_{RESNET,LONGCTX,FAMILIES,PIPELINE,SERVING}=0``
drop rows.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", ...}.
vs_baseline = achieved model FLOPs utilization / 0.35 (the target: >=35%
MFU for Transformer-base on v5e; >1.0 beats the target).

Model: Transformer-base WMT16 config (reference:
tests/unittests/dist_transformer.py ModelHyperParams — d_model 512,
d_inner 2048, 6+6 layers, 8 heads), trained with bf16 AMP, full step
(fwd + autodiff + Adam) as one XLA computation.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

from bench_common import log  # numpy only at import: no jax, no package

BATCH = int(os.environ.get("PT_BENCH_BATCH", "64"))
SEQ = int(os.environ.get("PT_BENCH_SEQ", "256"))
VOCAB = 10000
HERE = os.path.dirname(os.path.abspath(__file__))
CHILD_TIMEOUT_S = 900


def analytic_flops_per_step(cfg, batch, s, t):
    """Training FLOPs (fwd+bwd) per step: 6*flops_matmul_fwd with attention
    term; embedding lookups excluded."""
    d, di, L, h = cfg.d_model, cfg.d_inner, cfg.n_layer, cfg.n_head
    # per-layer matmul flops (fwd, mults*2):
    # qkv+out proj: 4 * 2*t*d*d ; ffn: 2 * 2*t*d*di ; attention: 2 * 2*h*t*t*(d/h)
    def layer_tokens(tok, t_kv):
        proj = 4 * 2 * tok * d * d
        ffn = 2 * 2 * tok * d * di
        attn = 2 * 2 * tok * t_kv * d
        return proj + ffn + attn

    enc = L * layer_tokens(batch * s, s)
    # decoder: self attn over t, cross attn over s (extra k/v proj + attn)
    dec_self = L * layer_tokens(batch * t, t)
    dec_cross = L * (2 * 2 * batch * t * d * d + 2 * 2 * batch * t * s * d)
    logits = 2 * batch * t * d * VOCAB
    fwd = enc + dec_self + dec_cross + logits
    return 3 * fwd  # bwd ~= 2x fwd


def transformer_row():
    """One transformer training row, measured in THIS process
    (``python bench.py --row``; shape from PT_BENCH_BATCH/PT_BENCH_SEQ)."""
    from bench_common import (
        attach_metrics,
        compile_with_oom_backoff,
        configure_process,
        enable_bench_metrics,
        measured_mfu,
        mfu,
        run_windows,
    )

    # metrics-only telemetry: the registry snapshot rides every BENCH
    # row's `metrics` field (PT_BENCH_METRICS=0 opts out)
    enable_bench_metrics()
    configure_process()
    import jax

    import paddle_tpu as fluid
    from paddle_tpu.models import transformer as T

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
    use_scan = os.environ.get("PT_BENCH_SCAN", "0") == "1"
    scan_unroll = int(os.environ.get("PT_BENCH_SCAN_UNROLL", "1"))
    main_prog, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main_prog, startup):
        model = (T.build_scan(cfg, unroll=scan_unroll) if use_scan
                 else T.build(cfg))
        fluid.optimizer.Adam(1e-4).minimize(model["loss"])
    log(f"layer mode: {'scan' if use_scan else 'unrolled'}")
    main_prog._amp = True  # bf16 matmuls, f32 master weights

    def make_exe():
        e = fluid.Executor()
        e.run(startup)
        return e

    # total exhaustion raises AllBatchesOOM: the row fails, non-zero exit
    exe, batch = compile_with_oom_backoff(
        make_exe,
        lambda e, b: e.run(main_prog,
                           feed=T.make_batch(cfg, b, SEQ, SEQ, seed=0),
                           fetch_list=[model["loss"]]),
        BATCH, floor=min(4, BATCH))

    # steady-state: feeds pre-staged on device, best-of-3 windows with one
    # sync per window (shared protocol, bench_common.run_windows)
    feeds = [
        {k: jax.device_put(v) for k, v in T.make_batch(cfg, batch, SEQ, SEQ,
                                                       seed=s).items()}
        for s in range(4)
    ]
    steps = 30
    best, mean = run_windows(exe, main_prog, model["loss"], feeds, steps)

    tokens_per_step = batch * SEQ  # target tokens (reference convention)
    tokens_per_sec = tokens_per_step * steps / best
    flops = analytic_flops_per_step(cfg, batch, SEQ, SEQ)
    mfu_best = mfu(flops, steps, best)
    mfu_mean = mfu(flops, steps, mean)
    # measured twin (roofline.py): XLA cost-analysis flops from the
    # compile report over the same best window — null when telemetry or
    # the report is off
    mfu_measured = measured_mfu(main_prog, best, steps)
    log(f"tokens/sec={tokens_per_sec:.0f}, analytic TFLOP/step={flops/1e12:.2f}, "
        f"MFU={mfu_best:.3f}, measured MFU={mfu_measured}")
    print(json.dumps(attach_metrics({
        "metric": "transformer_base_train_tokens_per_sec",
        "value": round(tokens_per_sec, 1),
        "unit": "tokens/sec",
        "vs_baseline": round(mfu_best / 0.35, 3),
        "value_mean": round(tokens_per_step * steps / mean, 1),
        "mfu_best": round(mfu_best, 4),
        "mfu_mean": round(mfu_mean, 4),
        "measured_mfu": mfu_measured,
    })))


class RowFailed(RuntimeError):
    """A child process exited non-zero, timed out, or printed no row."""


def run_child(argv, env_extra=None, timeout=CHILD_TIMEOUT_S):
    """Run one row in a fresh process (it alone holds the chip while it
    lives) and return the JSON row it printed last."""
    try:
        out = subprocess.run(argv, capture_output=True, text=True,
                             timeout=timeout,
                             env={**os.environ, **(env_extra or {})})
    except subprocess.TimeoutExpired as e:
        raise RowFailed(f"{argv[-1]}: no result in {timeout}s") from e
    sys.stderr.write(out.stderr)
    if out.returncode != 0:
        raise RowFailed(f"{argv[-1]}: exit code {out.returncode}")
    for line in reversed(out.stdout.splitlines()):
        line = line.strip()
        if line.startswith("{"):
            try:
                return json.loads(line)
            except ValueError:
                continue  # non-JSON line that happens to start with {
    raise RowFailed(f"{argv[-1]}: printed no JSON row")


def plan():
    """[(row key, argv, extra env)] in run order, from the PT_BENCH_*
    knobs. The headline comes first and is a child like the rest."""
    def on(knob):
        return os.environ.get(knob, "1") == "1"

    def script(name, *args):
        return [sys.executable, os.path.join(HERE, name), *args]

    rows = [("headline", script("bench.py", "--row"), {})]
    if on("PT_BENCH_RESNET"):
        rows.append(("resnet50", script("bench_resnet.py"), {}))
    if on("PT_BENCH_LONGCTX"):
        # long-context sweep at constant total tokens/step; t>=4096 rides
        # the in-kernel-causal flash path (no [t, t] tensor anywhere;
        # decoder-self dead blocks skipped)
        for t, bt in (("1024", "8"), ("4096", "2"), ("8192", "1")):
            rows.append((f"long_context_t{t}", script("bench.py", "--row"),
                         {"PT_BENCH_BATCH": bt, "PT_BENCH_SEQ": t}))
    if on("PT_BENCH_SERVING"):
        # continuous-batching decode: tokens/s + per-token latency
        # quantiles under a concurrency sweep through the serving
        # engine's prefill/decode split (zero fresh compiles after
        # warmup is the correctness rider)
        rows.append(("serving", script("bench_serving.py"), {}))
    if on("PT_BENCH_PIPELINE"):
        # sync vs pipelined trainer steady-state step time + the final
        # boundedness verdict mix (input/dispatch must be ~zero with
        # prefetch + sampled phases on)
        rows.append(("pipeline", script("bench_pipeline.py"), {}))
    if on("PT_BENCH_FAMILIES"):
        # remaining model families, one fresh process per family
        for key, fam, env in (
            ("se_resnext50", "se_resnext", {"PT_BENCH_BATCH": "128"}),
            ("bert_base", "bert", {"PT_BENCH_BATCH": "64",
                                   "PT_BENCH_SEQ": "128"}),
            ("deepfm", "deepfm", {"PT_BENCH_BATCH": "4096"}),
            ("ssd300", "ssd300", {"PT_BENCH_BATCH": "32"}),
        ):
            rows.append((key, script("bench_family.py"),
                         {"PT_BENCH_FAMILY": fam, **env}))
    return rows


def main(rows=None) -> int:
    """Run every planned row as a child in turn; print the merged row.
    Returns the exit code: 0 only if every child produced its row."""
    results, failed = {}, []
    for key, argv, env in (plan() if rows is None else rows):
        try:
            results[key] = run_child(argv, env)
        except RowFailed as e:
            log(f"bench row '{key}' FAILED: {e}")
            results[key] = None
            failed.append(key)
            continue
        if key.startswith("long_context_t"):
            results[key]["metric"] = (
                f"transformer_longctx_{key[len('long_context_'):]}"
                f"_tokens_per_sec")
        log(f"{key}: {results[key]}")
    row = dict(results.pop("headline", None) or {
        "metric": "transformer_base_train_tokens_per_sec", "value": None,
        "unit": "tokens/sec"})
    row.update(results)
    print(json.dumps(row))
    if failed:
        log(f"bench FAILED rows: {failed}")
        return 1
    return 0


if __name__ == "__main__":
    if sys.argv[1:] == ["--row"]:
        transformer_row()
    else:
        sys.exit(main())
