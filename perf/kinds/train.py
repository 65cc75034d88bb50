"""The train loop, the same for every train cell: feeds resident on the
device, one Executor.run per step, at most two steps in flight (before
step i is dispatched the loop waits for the loss of step i-2, as a
trainer that logs its loss does). A cell on several chips runs the same
program under CompiledProgram.with_data_parallel."""

from __future__ import annotations

import time

import numpy as np

from perf import harness, models
from perf.harness import say

IN_FLIGHT = 2
# sequences of the correctness sample: the loss is a mean over their
# real (transformer) or masked (BERT: 19 of 128 positions each) tokens,
# and the bf16 rounding of each position's logits averages out over
# several hundred of them
SAMPLE_SEQUENCES = 32
# eval-mode loss of the program (bf16 matmuls) against the float32
# reference, relative, for every family: dropping label smoothing
# moves the transformer's loss by 3e-3, dropping a layer BERT's by 3e-2
LOSS_REL_TOL = 1e-3


def run(run: harness.Run):
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    import paddle_tpu as fluid

    cell, cfg = run.cell, run.config
    traffic = cell["traffic"]
    chips = cell["chips"]
    fam = models.family(cfg)
    watch = harness.CompileWatch()
    harness.telemetry(run.traced)

    # --- set-up: weights on the device from the seed --------------------
    main, startup, evalp, loss, model = models.build_train(cfg, run.seed)
    scope = fluid.Scope()
    exe = fluid.Executor()
    t0 = time.perf_counter()
    exe.run(startup, scope=scope)
    jax.block_until_ready([scope.find_var(n) for n in scope.var_names()])
    run.first_calls["startup"] = time.perf_counter() - t0

    feeds_np = fam.feeds(cfg, traffic, run.seed)
    tokens = [fam.real_tokens(f) for f in feeds_np]

    # --- the correctness sample, outside the window ----------------------
    t0 = time.perf_counter()
    check_loss(run, exe, evalp, loss, scope, feeds_np[0])
    run.first_calls["eval_sample"] = time.perf_counter() - t0
    if hasattr(models.reference(cfg), "second_check"):
        t0 = time.perf_counter()
        check_second(run, exe, evalp, model, scope, feeds_np[0])
        run.first_calls["eval_second"] = time.perf_counter() - t0

    program = main
    sharding = None
    if chips > 1:
        program = fluid.CompiledProgram(main).with_data_parallel(
            loss_name=loss.name, devices=list(run.devices[:chips]))
        sharding = NamedSharding(program.mesh, P("data"))
    feeds = [{k: jax.device_put(v, sharding) if sharding is not None
              else jax.device_put(v) for k, v in f.items()}
             for f in feeds_np]

    def step(i):
        return exe.run(program, feed=feeds[i % len(feeds)],
                       fetch_list=[loss], scope=scope,
                       return_numpy=False)[0]

    def drain():
        jax.block_until_ready(
            [scope.find_var(n) for n in scope.var_names()])

    # --- warm-up: the cell's one program, twice --------------------------
    t0 = time.perf_counter()
    jax.block_until_ready(step(0))
    run.first_calls["train_step"] = time.perf_counter() - t0
    jax.block_until_ready(step(1))
    drain()
    harness.say_first_calls(run)

    # --- the measured window ----------------------------------------------
    compiles0 = watch.count
    run.setup_done()
    losses, t_begin = [], time.perf_counter()
    i = 0
    while True:
        if i >= IN_FLIGHT:
            jax.block_until_ready(losses[i - IN_FLIGHT])
            if time.perf_counter() - t_begin >= run.seconds:
                break
        losses.append(step(i + 2))   # feeds keep cycling after warm-up
        i += 1
    drain()  # the clock stops on the last dispatched step's state
    elapsed = time.perf_counter() - t_begin
    run.compiles_in_window = watch.count - compiles0
    run.counters["after"] = harness.program_counters()

    steps = len(losses)
    run.window = {
        "steps": steps, "seconds": elapsed,
        "tokens": sum(tokens[(j + 2) % len(feeds)] for j in range(steps)),
        "positions_per_step": traffic["batch"] * traffic["seq_len"],
        "flops_per_step": fam.train_flops(cfg, traffic["batch"],
                                          traffic["seq_len"]),
        "attention": fam.attention_cost(
            cfg, traffic["batch"] // chips, traffic["seq_len"]),
    }
    run.attempted, run.failed = steps, 0
    vals = np.asarray(jax.device_get(losses), np.float64)
    bad = int((~np.isfinite(vals)).sum())
    if bad:
        run.failed = bad
        run.problem(f"{bad} of {steps} losses in the window not finite")
    if run.compiles_in_window:
        run.problem(f"{run.compiles_in_window} compile(s) inside the "
                    f"measured window: its numbers are compile time")
    say(f"perf: window {steps} steps in {elapsed:.4f} s "
        f"({elapsed / steps * 1e3:.3f} ms/step, "
        f"{run.window['tokens'] / elapsed:.1f} real tokens/s), loss "
        f"{vals[0]:.4f} -> {vals[-1]:.4f}")

    # --- the device trace and the host phases: a stretch each, after
    # the window, so that neither probe sits inside what the other (or
    # the window) measures
    if run.traced:
        secs = cell.get("trace_seconds", 2.0)
        with harness.DeviceTrace(run):
            n = steady(step, drain, steps + 2, secs)
        run.window["traced_steps"] = n
        harness.say_trace(run, f"{n} steps")
        with harness.PhaseProbe(run):
            steady(step, drain, steps + 2 + n, secs)
    exe.close()
    harness.telemetry(False)


def steady(step, drain, first, seconds):
    """The window's loop again for ``seconds``: steps dispatched."""
    import jax

    t0, pend = time.perf_counter(), []
    while time.perf_counter() - t0 < seconds:
        if len(pend) >= IN_FLIGHT:
            jax.block_until_ready(pend[-IN_FLIGHT])
        pend.append(step(first + len(pend)))
    drain()
    return len(pend)


def sample_of(feed):
    """The first SAMPLE_SEQUENCES sequences of a feed."""
    return {k: np.asarray(v)[:SAMPLE_SEQUENCES] for k, v in feed.items()}


def check_loss(run, exe, evalp, loss, scope, feed):
    """Eval-mode clone of the program on SAMPLE_SEQUENCES sequences of
    the cell's length against the plain float32 reference on the same
    weights."""
    import jax
    import jax.numpy as jnp

    from perf.reference.common import weights_from_scope

    ref = models.reference(run.config)
    sample = sample_of(feed)
    got = float(np.asarray(exe.run(evalp, feed=sample, fetch_list=[loss],
                                   scope=scope)[0]))
    w = weights_from_scope(scope)
    with jax.default_matmul_precision("highest"):
        want = float(jax.jit(
            lambda w_, f_: ref.loss(w_, run.config, f_))(
                w, {k: jnp.asarray(v) for k, v in sample.items()}))
    del w
    rel = abs(got - want) / max(abs(want), 1e-9)
    run.check = {"program_loss": got, "reference_loss": want, "rel": rel}
    say(f"perf: correctness sample: program loss {got:.6f}, reference "
        f"{want:.6f}, relative difference {rel:.2e} (tolerance "
        f"{LOSS_REL_TOL})")
    if not (np.isfinite(got) and rel <= LOSS_REL_TOL):
        run.problem(f"eval loss {got} differs from the reference {want} "
                    f"by {rel:.2e} > {LOSS_REL_TOL}")


def check_second(run, exe, evalp, model, scope, feed):
    """The family's own second check, where its reference defines one:
    it can only add to ``check_loss``, whose verdict stands. The
    family file names what to fetch (``CHECK_FETCH``: keys of the dict
    its ``build_graph`` returns, each a variable or a list of them; a
    language model's would be the logits of the sample's last
    positions), the loop fetches that from the same eval clone on the
    same sample, and the reference's ``second_check(w, cfg, sample,
    fetched)`` (float32 weights, the sample's feed, {key: array or
    list of arrays}; run at "highest" matmul precision) returns
    (problems, record): each problem, in words, makes the run
    incorrect, the record is printed and kept as
    ``run.check["second"]``."""
    import jax

    from perf.reference.common import weights_from_scope

    sample = sample_of(feed)
    # (a Variable is a leaf to jax.tree: lists of them come back as lists)
    fetch, shape = jax.tree.flatten(
        {k: model[k] for k in models.family(run.config).CHECK_FETCH})
    fetched = jax.tree.unflatten(shape, [np.asarray(g) for g in exe.run(
        evalp, feed=sample, fetch_list=fetch, scope=scope)])
    w = weights_from_scope(scope)
    with jax.default_matmul_precision("highest"):
        problems, record = models.reference(run.config).second_check(
            w, run.config, sample, fetched)
    del w
    run.check["second"] = record
    say(f"perf: correctness sample, second check: {record}")
    for p in problems:
        run.problem(p)
