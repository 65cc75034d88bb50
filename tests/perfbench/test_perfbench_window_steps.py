"""perf/tools/window_steps.py: what its one line says of a window, on
events written by hand (a slow device, a slow host, one gap) and on a
tiny run of the harness's own loop."""

import importlib.util
import os
import sys

import pytest

from perf.kinds import train

import perfbench_tiny as tiny

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


@pytest.fixture(scope="module")
def tool():
    path, first = os.path.join(ROOT, "perf", "tools", "window_steps.py"), \
        sys.path[0]
    spec = importlib.util.spec_from_file_location("window_steps", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    sys.path[0] = first       # the script puts the checkout's root there
    return module


def window(step_s, run_s, n=10, late=None, gap_s=5.0):
    """The loop of perf/kinds/train.py by hand, two steps in flight: a
    step's dispatch takes ``run_s``, the device ``step_s`` a step, and
    step ``late`` (from 0) ``gap_s`` more."""
    events, now, done = [("wait", 0.0, 0.5)], 1.0, []

    def wait(j):
        nonlocal now
        events.append(("wait", now, max(now, done[j])))
        now = max(now, done[j])

    for i in range(n):
        if i >= 2:
            wait(i - 2)
        events.append(("run", now, now + run_s))
        now += run_s
        done.append(max(done[-1] if done else 0.0, now) + step_s
                    + (gap_s if i == late else 0.0))
    wait(n - 2)
    wait(n - 1)                                                # the drain
    return events, 1.0


@pytest.mark.parametrize("step_s,run_s,gap,says", [
    (0.165, 0.004, None, ("median 165.00 ms", "longest 165.00 ms",
                          "in Executor.run 0.040 s")),
    (0.635, 0.004, None, ("median 635.00 ms", "in Executor.run 0.040 s")),
    (0.001, 0.600, None, ("median 600.00 ms", "in Executor.run 6.000 s",
                          "in block_until_ready 0.001 s")),
    (0.165, 0.004, 4, ("median 165.00 ms", "longest 5165.00 ms (before step 5's)")),
], ids=["as-measured", "a-slow-device", "a-slow-host", "one-gap"])
def test_the_line_tells_a_slow_device_from_a_slow_host(tool, step_s, run_s,
                                                       gap, says):
    events, mark = window(step_s, run_s, late=gap)
    line = tool.summary(events, mark, [])
    assert line.startswith("window_steps: 10 steps in ")
    for part in says:
        assert part in line, (part, line)


def test_a_tiny_window_is_counted_as_the_harness_counts_it(tool, monkeypatch):
    import jax

    import paddle_tpu as fluid
    from perf import harness

    for owner, name in ((fluid.Executor, "run"), (jax, "block_until_ready"),
                        (harness.Run, "setup_done")):
        monkeypatch.setattr(owner, name, getattr(owner, name))  # put back
    watch = tool.Watch()
    cell = tiny.train_cell("phi4flash-train-s4096")
    run = tiny.make_run(cell, tiny.config(cell["config"]), seconds=0.3,
                        traced=False)
    train.run(run)
    line = watch.summary()
    steps, seconds = run.window["steps"], run.window["seconds"]
    assert line.startswith(f"window_steps: {steps} steps in ")
    assert float(line.split()[4]) == pytest.approx(seconds, abs=2e-3)
