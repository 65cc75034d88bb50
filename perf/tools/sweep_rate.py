"""python perf/tools/sweep_rate.py --workload <serve cell> [--seconds 24] [--rates a,b,c]

Finds the highest rate a serve cell's engine sustains, once, when the
cell is defined (PERF.md records the table). One process, one engine:
a closed-loop run with every slot kept full gives the engine's request
capacity; then open-loop windows at rates rising by a quarter from 0.4
of it, until a rate is not sustained: fewer than 99% of its requests
complete, or the number of requests in the system is still growing
when the window closes (more at the close than 1.15 x the number at
mid-window plus 8; the window has to be several times a request's
life, or the system is still filling at mid-window). The cell's file then fixes 4/5 of the last
sustained rate. Writes chiprun_out/sweep-<cell>.json. Needs a TPU."""

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[0] = ROOT


def in_system(recs, t):
    return sum(1 for r in recs if r["due"] <= t
               and (r["done_at"] is None or r["done_at"] > t))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=24.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--max-rates", type=int, default=8)
    ap.add_argument("--rates", default="",
                    help="comma-separated rates to try instead of the "
                         "closed-loop run and the rising series")
    args = ap.parse_args()

    from perf import data, harness, models, serve_stats, stats

    cell = harness.load_json("perf", "workloads", f"{args.workload}.json")
    cfg = harness.load_json("perf", "configs", f"{cell['config']}.json")
    devices = harness.require_tpu(cell["chips"])

    from paddle_tpu import jax_cache, serving

    jax_cache.configure()
    serve = models.kind("serve")
    pcfg, wscope = models.build_serve_weights(cfg, args.seed)
    e = cell["engine"]
    eng = serving.serve(pcfg, wscope, slots=e["slots"],
                        src_len=e["src_len"], max_len=e["max_len"],
                        queue_depth=e["queue_depth"])
    eng.submit([5, 6, 7], max_new_tokens=3)
    eng.run_until_idle()

    def window(rate, closed):
        traffic = dict(cell["traffic"], rate_per_s=rate)
        run = harness.Run({}, cell, cfg, args.seed, args.seconds, False,
                          time.perf_counter())
        reqs = data.serve_requests(cfg, traffic, args.seed, args.seconds)
        s0 = eng.stats()
        recs, elapsed = serve.drive(eng, reqs, args.seconds,
                                    traffic.get("drain_seconds", 10.0),
                                    closed_loop=closed)
        eng.run_until_idle()
        s1 = eng.stats()
        run.window = {"requests": recs, "seconds": args.seconds}
        serve.judge(run, recs)
        done = sum(1 for r in recs if r["ok"])
        row = {
            "rate_per_s": rate, "closed_loop": closed,
            "requests": len(recs), "completed_share": done / len(recs),
            "in_system_mid": in_system(recs, args.seconds / 2),
            "in_system_close": in_system(recs, args.seconds),
            "last_ended_s": elapsed,
            "ttft_ms": stats.tail(serve_stats.ttft_ms(run)),
            "token_gap_ms": stats.tail(serve_stats.token_gaps_ms(run)),
            "late_ms": stats.tail(serve_stats.late_ms(run)),
            "tokens_per_s": (s1["tokens_emitted"] - s0["tokens_emitted"])
            / min(elapsed, args.seconds + 1e-9),
            "occupancy": (s1["tokens_emitted"] - s0["tokens_emitted"])
            / max(1, (s1["decode_steps"] - s0["decode_steps"])
                  * e["slots"]),
            "memory_peak_bytes": harness.peak_memory_bytes(devices[:1]),
        }
        row["sustained"] = (
            row["completed_share"] >= 0.99
            and row["in_system_close"] <= 1.15 * row["in_system_mid"] + 8)
        harness.say("sweep: " + json.dumps(row))
        return row

    rows, best, capacity = [], None, None
    if args.rates:
        series = [float(r) for r in args.rates.split(",")]
    else:
        rows.append(window(1500.0, True))
        # steady completions: the first fill of the slots is not a rate
        capacity = (rows[0]["requests"] - e["slots"]) / args.seconds
        series = [0.5 * capacity * 1.25 ** i for i in range(args.max_rates)]
    for rate in series:
        row = window(rate, False)
        rows.append(row)
        if not row["sustained"]:
            break
        best = rate
    out = {"cell": cell["name"], "device": devices[0].device_kind,
           "closed_loop_requests_per_s": capacity,
           "highest_sustained_rate": best,
           "four_fifths": None if best is None else 0.8 * best,
           "rows": rows}
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out",
                           f"sweep-{cell['name']}.json"), "w") as f:
        json.dump(out, f, indent=1)
    harness.say("sweep: " + json.dumps(
        {k: v for k, v in out.items() if k != "rows"}))
    eng.close()


if __name__ == "__main__":
    main()
