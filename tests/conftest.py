"""Test env: simulated 8-device CPU mesh.

The TPU analog of the reference's multi-process-on-localhost distributed
test pattern (reference: tests/unittests/test_dist_base.py:311): sharding
semantics are validated on a virtual CPU mesh (SURVEY.md section 4
implication (c)).

``PT_TEST_TPU=1`` leaves jax on its default (real TPU) backend instead:
the mode of the hardware kernel suite, tests/test_flash_attention_tpu.py,
run on the chip in one pytest process.
"""

import faulthandler
import os
import signal
import tempfile

import jax
import pytest

if os.environ.get("PT_TEST_TPU") != "1":
    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", 8)
    # Numeric-gradient checks need f64 reference arithmetic.
    jax.config.update("jax_enable_x64", True)
    # Tests are compile-bound on the CPU backend (hundreds of tiny jits);
    # dialing XLA optimization down trades irrelevant runtime for compile
    # time. Opt out with PT_TEST_FULL_OPT=1 (e.g. for perf-sensitive
    # debugging).
    if os.environ.get("PT_TEST_FULL_OPT") != "1":
        jax.config.update("jax_disable_most_optimizations", True)

# Persistent compile cache: repeat suite runs skip the slow XLA compiles.
from paddle_tpu import flags, jax_cache, monitor  # noqa: E402

jax_cache.configure()

# The perfbench files' tiny traced runs trace into perf.harness's one
# directory a cell, which DeviceTrace empties on entry: two workers of
# one run delete each other's trace (ROADMAP Queue 3 item 2 (19): the
# files that do not redirect it are the benchmark's). A directory a
# worker.
if os.environ.get("PYTEST_XDIST_WORKER"):
    from perf import harness as _perf_harness  # noqa: E402

    _perf_harness.TRACE_ROOT = os.path.join(
        _perf_harness.TRACE_ROOT, os.environ["PYTEST_XDIST_WORKER"])


# --- a time limit a test ---
#
# Seconds one test (setup, call and teardown together) may take off the
# run's clock. No option, no variable, no marker raises it: a test that
# needs more is a test to cut. The slowest test of the whole run PR 68
# started from took 64 s under six workers
# (tests/test_ring_attention.py::test_ring_attention_dropout, 14 s since),
# the slowest of its own tree 42 s (a chip_smoke phase).
# The multi-process drills hold their children to a deadline of their
# own (tests/test_fleet_*: 150 s), which is the shorter and ends them
# first.
TEST_LIMIT_S = 240.0
_STDERR_FD = 2      # (pytest_configure: the run's own, behind the capture)


def _all_stacks():
    with tempfile.TemporaryFile(mode="w+") as f:
        faulthandler.dump_traceback(file=f, all_threads=True)
        f.seek(0)
        return f.read()


@pytest.hookimpl(wrapper=True, tryfirst=True)
def pytest_runtest_protocol(item):
    """A hang prints ``F`` with the test's name and every thread's stack,
    and the worker goes on to its next test. SIGALRM reaches Python code
    only between bytecodes: a hang inside native code ends the worker at
    twice the limit (faulthandler's watchdog thread needs no bytecode),
    which xdist reports as the test's crash before it starts another.
    (pytest and xdist's workers run tests in their main thread, where
    a signal's handler may be set.)"""
    def expired(signum, frame):
        pytest.fail(
            f"{item.nodeid} took more than the {TEST_LIMIT_S:g} s a test "
            f"may take (tests/conftest.py TEST_LIMIT_S). Every thread's "
            f"stack:\n{_all_stacks()}", pytrace=False)

    before = signal.signal(signal.SIGALRM, expired)
    signal.setitimer(signal.ITIMER_REAL, TEST_LIMIT_S)
    faulthandler.dump_traceback_later(2 * TEST_LIMIT_S, exit=True,
                                      file=_STDERR_FD)
    try:
        return (yield)
    finally:
        faulthandler.cancel_dump_traceback_later()
        signal.setitimer(signal.ITIMER_REAL, 0)
        signal.signal(signal.SIGALRM, before)


@pytest.fixture(autouse=True)
def _zeroed_metrics():
    """A test starts from zeroed metrics. The monitor's rows (the
    dispatch counters among them) are process-wide, and a file's traced
    run used to leave its rows to whatever its xdist worker ran next:
    tests/test_grouped_matmul_adam.py's ``..._where_the_call_has_a_tile
    [adam]`` read tests/test_checkpoint.py's grouped matmuls (PR 68).
    And it leaves ``telemetry`` as it found it: while the flag is on the
    monitor holds a process-wide hook in ``gc.callbacks``, which a test
    that left the flag on used to leave to every test after it."""
    monitor.reset()
    telemetry = flags.get_flag("telemetry")
    yield
    flags.set_flags({"telemetry": telemetry})


# --- suite tiering (VERDICT r4 item 3) ---
#
# Two tiers: the default SMOKE tier is the cross-round regression gate
# (<8 min cold on the builder box; every subsystem keeps at least one
# cheap representative); the FULL tier adds the expensive deep-parity
# tests (multi-axis loss parity, big one-step model compiles, spec
# oracles). Run everything with `pytest --full` or PT_TEST_TIER=full.


def pytest_addoption(parser):
    parser.addoption(
        "--full", action="store_true", default=False,
        help="run the full tier (includes tests marked 'full')")


def pytest_configure(config):
    global _STDERR_FD
    _STDERR_FD = os.dup(2)      # capture is suspended while plugins configure
    config.addinivalue_line(
        "markers",
        "full: expensive deep-parity test, excluded from the default "
        "smoke tier (run with --full or PT_TEST_TIER=full)")
    config.addinivalue_line(
        "markers",
        "slow: long-running end-to-end test, excluded from the tier-1 "
        "regression gate (which runs -m 'not slow')")
    config.addinivalue_line(
        "markers",
        "chaos: multi-process chaos drill (fault-plan-driven kills, "
        "re-exec recovery) — deterministic but expensive; deselected "
        "from every default tier, run with -m chaos")
    config.addinivalue_line(
        "markers",
        "serving_e2e: serving-plane end-to-end drill at full slot "
        "counts (continuous batching vs solo-decode parity); the "
        "heavyweight ones also carry 'slow' — select the family with "
        "-m serving_e2e")
    config.addinivalue_line(
        "markers",
        "multidevice_fragile: quarantined under the environment's glibc "
        "heap-corruption crash (seeded by 8-device pjit executions; "
        "reproduces at the seed tree — see ROADMAP watch item). The "
        "corruption is heap-layout-sensitive, so the abort can land "
        "either in a TP-sharded pjit execution itself or in a "
        "downstream test's ordinary allocations; tests where a full "
        "tier-1 run deterministically dies carry this marker. "
        "Deselected by default; run with PT_TEST_MULTIDEVICE=1 or an "
        "explicit -m expression")


def pytest_collection_modifyitems(config, items):
    markexpr = getattr(config.option, "markexpr", "") or ""
    # The multidevice_fragile quarantine applies to EVERY tier: the
    # crash aborts the whole process (no pytest report survives it), so
    # even --full runs skip these unless explicitly opted in.
    drop = set()
    if os.environ.get("PT_TEST_MULTIDEVICE") != "1" and \
            "multidevice_fragile" not in markexpr:
        drop.add("multidevice_fragile")
    # chaos drills spawn whole process fleets: never part of a default
    # tier (including --full); select explicitly with -m chaos
    if "chaos" not in markexpr:
        drop.add("chaos")
    if not (config.getoption("--full")
            or os.environ.get("PT_TEST_TIER") == "full"):
        # default smoke tier drops 'full' AND 'slow' (unless the
        # caller's -m expression names 'slow' explicitly, e.g. `-m slow`
        # to run only the end-to-end tests)
        drop.add("full")
        if "slow" not in markexpr:
            drop.add("slow")
    dropped = [it for it in items if drop & set(it.keywords)]
    if dropped:
        config.hook.pytest_deselected(items=dropped)
        dropped_set = set(dropped)
        items[:] = [it for it in items if it not in dropped_set]
