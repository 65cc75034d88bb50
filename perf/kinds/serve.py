"""The open-loop serve loop: ONE thread submits what is due, calls
``eng.step()``, stamps the tokens it handed out, and sleeps only when
the engine is idle and nothing is due. Every latency counts from the
instant a request was DUE, not from when the loop got round to
submitting it, so a stall lengthens the latencies of what it delayed.

``closed_loop`` in the cell's traffic (used once, by the sweep's
capacity run; no cell of the benchmark sets it) keeps every slot full
instead of following a schedule."""

from __future__ import annotations

import time

import numpy as np

from perf import data, harness, models, serve_stats, stats
from perf.harness import say

# the engine's top logit against the reference's logit for the same
# token on the same prefix, relative to the larger of the two: five bf16
# epsilons (PERF.md finding 6: the engine's matmuls are one bf16 pass,
# 3.6e-3 seen between two engine geometries on the v5e)
LOGIT_REL_TOL = 0.02
SAMPLE_SRC_LENS = (24, 61)
SAMPLE_NEW_TOKENS = 12


def run(run: harness.Run):
    from paddle_tpu import serving

    cell, cfg = run.cell, run.config
    traffic = cell["traffic"]
    eng_kw = cell["engine"]
    watch = harness.CompileWatch()
    harness.telemetry(False)

    # --- set-up: weights from the seed, the engine, its two programs ----
    t0 = time.perf_counter()
    pcfg, wscope = models.build_serve_weights(cfg, run.seed)
    run.first_calls["startup"] = time.perf_counter() - t0
    eng = serving.serve(pcfg, wscope, slots=eng_kw["slots"],
                        src_len=eng_kw["src_len"], max_len=eng_kw["max_len"],
                        queue_depth=eng_kw["queue_depth"])
    t0 = time.perf_counter()
    warm = eng.submit(np.arange(3, 3 + 8, dtype=np.int64), max_new_tokens=3)
    eng.run_until_idle()
    run.first_calls["prefill_and_decode"] = time.perf_counter() - t0
    if warm.outcome not in ("completed", "length"):
        run.problem(f"warm-up request ended '{warm.outcome}'")

    t0 = time.perf_counter()
    check_logits(run, eng, wscope)
    run.first_calls["logit_sample"] = time.perf_counter() - t0
    harness.say_first_calls(run)

    # --- the measured window ---------------------------------------------
    harness.telemetry(run.traced)
    reqs = data.serve_requests(cfg, traffic, run.seed, run.seconds)
    stats0 = eng.stats()
    compiles0 = watch.count
    run.setup_done()
    recs, elapsed = drive(eng, reqs, run.seconds,
                          traffic.get("drain_seconds", 10.0),
                          closed_loop=traffic.get("closed_loop", False))
    run.compiles_in_window = watch.count - compiles0
    stats1 = eng.stats()
    run.counters["after"] = harness.program_counters()
    run.window = {
        "requests": recs, "seconds": run.seconds, "elapsed": elapsed,
        "slots": eng_kw["slots"],
        "decode_steps": stats1["decode_steps"] - stats0["decode_steps"],
        "tokens_emitted": stats1["tokens_emitted"]
        - stats0["tokens_emitted"],
        "backlog_at_close": sum(1 for r in recs if r["done_at"] is None
                                or r["done_at"] > run.seconds),
    }
    judge(run, recs)
    report(run, recs)

    # --- the device trace and the host phases: a stretch each, after
    # the window, so that neither probe sits inside what the other (or
    # the window) measures
    if run.traced:
        secs = cell.get("trace_seconds", 2.0)
        closed = traffic.get("closed_loop", False)
        more = data.serve_requests(cfg, traffic, run.seed + 1, secs)
        with harness.DeviceTrace(run):
            drive(eng, more, secs, 0.5, closed_loop=closed)
        eng.run_until_idle()
        harness.say_trace(run, f"{len(more)} requests")
        with harness.PhaseProbe(run):
            drive(eng, more, secs, 0.5, closed_loop=closed)
        eng.run_until_idle()
    eng.close()
    harness.telemetry(False)


def drive(eng, reqs, seconds, drain_seconds, closed_loop=False):
    """Run the schedule. Returns (one record per request, seconds until
    the last of them ended). A record: due, submitted (both seconds
    from the window's start), admit_ts, stamps (when each token was
    handed out), done_at, outcome, owed."""
    recs = [{"due": r["due"], "submitted": None, "admit": None,
             "stamps": [], "done_at": None, "outcome": None,
             "owed": r["max_new"], "handle": None} for r in reqs]
    n, nxt = len(reqs), 0
    live = []                       # records whose handle is not done
    limit = seconds + drain_seconds
    t0 = time.perf_counter()

    def due(now):
        if nxt >= n:
            return False
        if closed_loop:
            # every free slot refilled at once, until the window closes
            return len(live) < eng.slots and now < seconds
        return reqs[nxt]["due"] <= now

    while True:
        now = time.perf_counter() - t0
        while due(now):
            rec = recs[nxt]
            if closed_loop:
                rec["due"] = now    # "due" when it is submitted
            try:
                rec["handle"] = eng.submit(
                    reqs[nxt]["src"], max_new_tokens=reqs[nxt]["max_new"])
                rec["submitted"] = time.perf_counter() - t0
                live.append(rec)
            except Exception as e:  # refused: counts as failed
                rec["outcome"] = f"refused: {type(e).__name__}"
            nxt += 1
            now = time.perf_counter() - t0
        if live:
            eng.step()
            t = time.perf_counter() - t0
            still = []
            for rec in live:
                h = rec["handle"]
                got = len(h.tokens) - len(rec["stamps"])
                if got > 0:
                    rec["stamps"].extend([t] * got)
                if h.done:
                    rec["done_at"] = t
                else:
                    still.append(rec)
            live = still
        elif nxt >= n or (closed_loop and now >= seconds):
            break
        else:
            time.sleep(min(max(reqs[nxt]["due"] - now, 0.0), 0.0005))
        if now > limit:
            break
    elapsed = time.perf_counter() - t0
    for rec in recs:
        h = rec.pop("handle")
        if h is not None:
            rec["outcome"] = h.outcome
            rec["tokens"] = len(h.tokens)
            if h.admit_ts is not None:
                rec["admit"] = h.admit_ts - t0
    if closed_loop:
        recs = [r for r in recs if r["submitted"] is not None]
    return recs, elapsed


def judge(run, recs):
    """Every request due in the window is attempted; one that was
    refused, failed, or is without its tokens when the run ends has
    failed."""
    run.attempted = len(recs)
    for r in recs:
        # "length": it got every token it was owed; "completed": the
        # end token came first (it is dropped, so even none may remain)
        ok = (r["outcome"] == "length" and r.get("tokens") == r["owed"]) \
            or (r["outcome"] == "completed"
                and r.get("tokens", 0) <= r["owed"])
        r["ok"] = bool(ok)
    run.failed = sum(1 for r in recs if not r["ok"])
    if run.failed:
        worst = next(r for r in recs if not r["ok"])
        run.problem(f"{run.failed} of {len(recs)} requests failed, e.g. "
                    f"outcome {worst['outcome']} with "
                    f"{worst.get('tokens')} of {worst['owed']} tokens")
    if run.compiles_in_window:
        run.problem(f"{run.compiles_in_window} compile(s) inside the "
                    f"measured window: its numbers are compile time")


def report(run, recs):
    """The earlier lines: medians beside the tails, sample counts."""
    w = run.window
    say(f"perf: window {len(recs)} requests, {run.failed} failed, last "
        f"ended at {w['elapsed']:.3f} s; backlog when the window closed "
        f"{w['backlog_at_close']}; {w['tokens_emitted']} tokens in "
        f"{w['decode_steps']} decode steps")
    for name, vals in (("ttft_ms", serve_stats.ttft_ms(run)),
                       ("token_gap_ms", serve_stats.token_gaps_ms(run)),
                       ("late_ms", serve_stats.late_ms(run)),
                       ("queue_wait_ms", serve_stats.queue_wait_ms(run))):
        t = stats.tail(vals)
        say(f"perf:   {name}: n {t['n']} p50 {t['p50']} p95 {t['p95']}")


def check_logits(run, eng, wscope):
    """Two requests through the engine (prefill, then decode through
    the cache) against the reference's full forward on the same prefix:
    at each step the engine's top logit and the reference's logit for
    the token the engine chose, and the reference's own top logit, all
    within LOGIT_REL_TOL. The engine exposes its logit only on the
    request trace, so telemetry and the trace are on for this sample
    and off again after it."""
    import jax

    from paddle_tpu import monitor

    from perf.reference.common import weights_from_scope

    ref = models.reference(run.config)
    harness.telemetry(True, request_trace=True)
    r = np.random.RandomState(run.seed % (2 ** 32))
    srcs = [r.randint(3, run.config["src_vocab_size"],
                      (min(n, eng.src_len),)).astype(np.int64)
            for n in SAMPLE_SRC_LENS]
    new = min(SAMPLE_NEW_TOKENS, eng.max_len - 1)
    handles = [eng.submit(s, max_new_tokens=new) for s in srcs]
    eng.run_until_idle()
    steps = {}
    for ev in monitor.trace_events():
        if ev["name"] == "decode" and ev["cat"] == "request":
            a = ev["args"]
            steps.setdefault(a["req"], []).append(
                (a["step"], a["token"], a["logit"]))
    harness.telemetry(False)
    w = weights_from_scope(wscope)
    worst = 0.0
    full_forward = jax.jit(
        lambda w_, src_, toks_: ref.greedy_logits(
            w_, run.config, src_, toks_, bos_id=eng.bos_id))
    with jax.default_matmul_precision("highest"):
        for h, src in zip(handles, srcs):
            got = [(tok, lg) for _, tok, lg in
                   sorted(steps.get(h.trace_id, []))]
            if h.outcome not in ("completed", "length") or not got:
                run.problem(f"sample request ended '{h.outcome}' with "
                            f"{len(got)} traced decode steps")
                continue
            toks = [t for t, _ in got]
            if toks[:len(h.tokens)] != list(h.tokens):
                run.problem(f"sample request: trace tokens {toks} != "
                            f"handle tokens {h.tokens}")
            logits = np.asarray(full_forward(w, src, np.asarray(toks)))
            for i, (tok, lg) in enumerate(got):
                want, top = float(logits[i, tok]), float(logits[i].max())
                scale = max(abs(lg), abs(top), 1e-6)
                worst = max(worst, abs(lg - want) / scale,
                            abs(top - want) / scale)
    del w
    run.check = {"logit_rel": worst}
    say(f"perf: correctness sample: {len(srcs)} requests x {new} decode "
        f"steps, engine vs reference logits differ by at most "
        f"{worst:.2e} of their size (tolerance {LOGIT_REL_TOL})")
    if not worst <= LOGIT_REL_TOL:
        run.problem(f"engine logits differ from the reference by "
                    f"{worst:.2e} > {LOGIT_REL_TOL}")
