"""python perf/tools/window_steps.py --workload <cell> --seed <n> --seconds <s>

perf/run.py's untraced run of a train cell, with the host's clock read
around every ``Executor.run`` and every ``jax.block_until_ready`` of the
measured window and this process's CPU seconds sampled twice a second
(the chip machine's /proc/stat and load average stand still: my chip
run, PR 40): where a window is slow, whether the time went into
dispatching steps (the host), into waiting for their losses (the device,
or whatever holds it), or into one gap. The run's own lines are printed
as perf/run.py prints them; one more line in front of the last says, for
the window: steps, the median and the longest distance between two
losses, the seconds inside ``Executor.run`` and inside
``block_until_ready`` and this process's CPU seconds. The events go to
chiprun_out/steps-<cell>-<seed>.json. Written for PERF.md section 7
(21): one run of `phi4flash-train-s4096` in 68 at a quarter of the rate.
"""

import json
import os
import statistics
import sys
import threading
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[0] = ROOT


class Watch:
    """The clock around every ``Executor.run`` and
    ``jax.block_until_ready`` from now on, the start of the window
    (``Run.setup_done``) and the process's CPU seconds twice a second."""

    def __init__(self):
        import jax

        import paddle_tpu as fluid
        from perf import harness

        self.events, self.mark, self.samples = [], [], []
        self.done = threading.Event()
        fluid.Executor.run = self.timed("run", fluid.Executor.run)
        jax.block_until_ready = self.timed("wait", jax.block_until_ready)
        plain = harness.Run.setup_done

        def setup_done(run):
            self.mark.append(time.perf_counter())
            return plain(run)

        harness.Run.setup_done = setup_done
        threading.Thread(target=self.sample, daemon=True).start()

    def timed(self, kind, fn):
        def call(*args, **kwargs):
            t0 = time.perf_counter()
            out = fn(*args, **kwargs)
            self.events.append((kind, t0, time.perf_counter()))
            return out
        return call

    def sample(self):
        while not self.done.wait(0.5):
            self.samples.append((time.perf_counter(), sum(os.times()[:2])))

    def summary(self):
        self.done.set()
        return summary(self.events, self.mark[0], self.samples)


def main():
    from perf import run as perf_run

    watch = Watch()

    def report(line, **kw):   # the summary goes in front of the last line
        if isinstance(line, str) and line.startswith("{") and watch.mark:
            print(watch.summary(), flush=True)
        print(line, **kw)

    perf_run.print = report
    argv = sys.argv[1:] + ["--trace", "0"]
    rc = perf_run.main(argv)
    cell, seed = argv[argv.index("--workload") + 1], argv[
        argv.index("--seed") + 1]
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out",
                           f"steps-{cell}-{seed}.json"), "w") as f:
        json.dump({"mark": watch.mark, "events": watch.events,
                   "samples": watch.samples}, f)
    return rc


def summary(events, mark, samples):
    inside = [e for e in events if e[1] >= mark]
    # an untraced run: a step each Executor.run, a loss each wait but
    # the last, which is the drain the window ends with
    runs = [e for e in inside if e[0] == "run"]
    waits = [e for e in inside if e[0] == "wait"]
    end = waits[-1][2]
    done_at = [e[2] for e in waits[:-1]]
    gaps = [b - a for a, b in zip(done_at, done_at[1:])]
    near = [s for s in samples if mark <= s[0] <= end]
    cpu = ""
    if len(near) >= 2:
        cpu = f", process cpu {near[-1][1] - near[0][1]:.2f} s"
    return (f"window_steps: {len(runs)} steps in {end - mark:.3f} s; between "
            f"losses median {statistics.median(gaps) * 1e3:.2f} ms, longest "
            f"{max(gaps) * 1e3:.2f} ms (before step "
            f"{gaps.index(max(gaps)) + 2}'s); "
            f"in Executor.run {sum(e[2] - e[1] for e in runs):.3f} s "
            f"(longest {max(e[2] - e[1] for e in runs) * 1e3:.2f} ms), in "
            f"block_until_ready {sum(e[2] - e[1] for e in waits):.3f} s{cpu}")


if __name__ == "__main__":
    sys.exit(main())
