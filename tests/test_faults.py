"""Chaos suite: deterministic fault injection (paddle_tpu/faults.py).

Acceptance (ISSUE 5): seeded plans replay exactly, sites arm/disarm
live via the ``fault_plan`` flag, every injection is metered, and the
disabled path allocates nothing (tracemalloc proof, like PRs 1-4)."""

import time
import tracemalloc

import pytest

import paddle_tpu as fluid  # noqa: F401 — registers all builtin sites
from paddle_tpu import faults, flags, monitor


@pytest.fixture(autouse=True)
def _clean():
    faults.disarm()
    yield
    faults.disarm()
    flags.set_flags({"fault_plan": "", "telemetry": False})


# --------------------------------------------------------------------------
# plan parsing
# --------------------------------------------------------------------------

def test_plan_parses_all_action_forms():
    faults.arm("s1:raise@1;s2:raise(boom)@2;s3:delay(0.01)@1,3;"
               "s4:truncate(16)@1;s5:raise@p0.5", seed=0)
    assert faults.active()


@pytest.mark.parametrize("bad", [
    "no_colon@1", "s:frobnicate@1", "s:raise", "s:raise@",
])
def test_bad_plan_entries_raise(bad):
    with pytest.raises(ValueError):
        faults.arm(bad)


def test_empty_plan_means_disarmed():
    faults.arm("")
    assert not faults.active()


# --------------------------------------------------------------------------
# Nth-hit determinism
# --------------------------------------------------------------------------

def test_raise_fires_at_exactly_the_nth_hit():
    faults.arm("det.site:raise@3")
    s = faults.site("det.site")
    s.hit()
    s.hit()
    with pytest.raises(faults.InjectedFault) as ei:
        s.hit()
    assert ei.value.site == "det.site" and ei.value.hit == 3
    s.hit()  # fires ONLY at the 3rd
    assert [r["hit"] for r in faults.records()] == [3]


def test_multiple_triggers_and_message():
    faults.arm("m.site:raise(kaboom)@1,3")
    s = faults.site("m.site")
    with pytest.raises(faults.InjectedFault, match="kaboom"):
        s.hit()
    s.hit()
    with pytest.raises(faults.InjectedFault):
        s.hit()


def test_delay_action_sleeps():
    faults.arm("slow.site:delay(0.05)@2")
    s = faults.site("slow.site")
    t0 = time.perf_counter()
    s.hit()
    fast = time.perf_counter() - t0
    t0 = time.perf_counter()
    s.hit()
    slow = time.perf_counter() - t0
    assert slow >= 0.05 > fast


def test_truncate_action_tears_the_file(tmp_path):
    p = tmp_path / "payload.bin"
    p.write_bytes(b"x" * 100)
    faults.arm("torn.site:truncate(7)@1")
    faults.site("torn.site").hit(path=str(p))
    assert p.stat().st_size == 7
    # a hit with no path safely skips truncation
    faults.site("torn.site").hit()


# --------------------------------------------------------------------------
# seeded probabilistic plans replay exactly
# --------------------------------------------------------------------------

def _fire_pattern(seed, n=200):
    faults.arm("p.site:raise@p0.3", seed=seed)
    s = faults.site("p.site")
    pattern = []
    for _ in range(n):
        try:
            s.hit()
            pattern.append(0)
        except faults.InjectedFault:
            pattern.append(1)
    return pattern


def test_seeded_probability_is_deterministic():
    a = _fire_pattern(seed=11)
    b = _fire_pattern(seed=11)
    assert a == b
    assert 0 < sum(a) < len(a)  # actually probabilistic, not all/none
    c = _fire_pattern(seed=12)
    assert a != c  # a different seed gives a different replay


def test_per_site_streams_are_independent():
    faults.arm("pa:raise@p0.5;pb:raise@p0.5", seed=3)

    def pattern(name):
        s = faults.site(name)
        out = []
        for _ in range(64):
            try:
                s.hit()
                out.append(0)
            except faults.InjectedFault:
                out.append(1)
        return out

    assert pattern("pa") != pattern("pb")


# --------------------------------------------------------------------------
# flag wiring + disarm
# --------------------------------------------------------------------------

def test_flag_arms_and_disarms_live():
    flags.set_flags({"fault_plan": "flag.site:raise@1"})
    assert faults.active()
    with pytest.raises(faults.InjectedFault):
        faults.site("flag.site").hit()
    flags.set_flags({"fault_plan": ""})
    assert not faults.active()
    faults.site("flag.site").hit()  # disarmed: no-op


def test_seed_flag_write_does_not_drop_programmatic_plan():
    """set_flags({'fault_seed': ...}) fires the plan watcher; with
    fault_plan still empty it must NOT disarm a faults.arm()'d plan
    (code-review finding, round 4)."""
    faults.arm("keep.site:raise@2")
    flags.set_flags({"fault_seed": 7})
    assert faults.active()
    s = faults.site("keep.site")
    s.hit()
    with pytest.raises(faults.InjectedFault):
        s.hit()  # hit counters also survived the flag write
    # the flag path still disarms what the flag armed
    flags.set_flags({"fault_plan": "keep.site:raise@1", "fault_seed": 8})
    flags.set_flags({"fault_plan": ""})
    assert not faults.active()


def test_records_survive_disarm_for_postmortems():
    """The natural chaos pattern disarms in a finally block and THEN
    asserts on records() — the log must survive disarm and reset only
    at the next arm (code-review finding, round 6)."""
    faults.arm("pm.site:raise@1")
    with pytest.raises(faults.InjectedFault):
        faults.site("pm.site").hit()
    faults.disarm()
    assert [r["site"] for r in faults.records()] == ["pm.site"]
    faults.arm("pm.site:raise@1")  # fresh plan, fresh log
    assert faults.records() == []
    faults.disarm()


def test_disarm_resets_hit_counters():
    faults.arm("r.site:raise@2")
    faults.site("r.site").hit()
    faults.disarm()
    faults.arm("r.site:raise@2")
    s = faults.site("r.site")
    s.hit()  # counters restarted: this is hit 1 again, no fire
    with pytest.raises(faults.InjectedFault):
        s.hit()


def test_builtin_sites_registered():
    # production sites declared at import of their modules
    import paddle_tpu.contrib.trainer  # noqa: F401
    import paddle_tpu.incubate.fleet.fleet_base  # noqa: F401
    import paddle_tpu.io  # noqa: F401
    import paddle_tpu.parallel.checkpoint  # noqa: F401

    names = set(faults.sites())
    assert {"ckpt.write_shards", "ckpt.commit", "ckpt.read",
            "fleet.kv_get", "fleet.kv_put", "fleet.connect",
            "fleet.heartbeat", "fleet.resize",
            "reader.next", "io.export"} <= names
    # the documented registry stays in sync with the declarations
    assert set(faults.BUILTIN_SITES) <= names


# --------------------------------------------------------------------------
# metrics
# --------------------------------------------------------------------------

def test_all_fault_plane_instruments_registered_for_scrape():
    """ISSUE 5 acceptance: every fault/retry/checkpoint instrument is
    registered eagerly (module import), so a /metrics scrape (which
    serves to_prometheus) shows the full set."""
    import paddle_tpu.contrib.trainer  # noqa: F401
    import paddle_tpu.parallel.checkpoint  # noqa: F401
    import paddle_tpu.retry  # noqa: F401

    text = monitor.to_prometheus()
    for name in ("pt_fault_injected_total", "pt_retry_total",
                 "pt_ckpt_commit_seconds", "pt_ckpt_invalid_skipped_total",
                 "pt_ckpt_async_errors_total",
                 "pt_trainer_auto_resumes_total"):
        assert f"# TYPE {name}" in text, name


def test_injections_are_metered_and_exported():
    monitor.enable()
    faults.arm("met.site:raise@1,2")
    s = faults.site("met.site")
    for _ in range(2):
        with pytest.raises(faults.InjectedFault):
            s.hit()
    c = monitor.counter("pt_fault_injected_total")
    assert c.value(labels={"site": "met.site"}) == 2
    assert 'pt_fault_injected_total{site="met.site"} 2' in \
        monitor.to_prometheus()


# --------------------------------------------------------------------------
# zero-overhead disabled path
# --------------------------------------------------------------------------

def test_disarmed_hit_allocates_nothing():
    """Sites live in hot code (reader.next fires per trainer batch):
    while no plan is armed a hit must be one boolean check."""
    assert not faults.active()
    s = faults.site("hot.site")
    for _ in range(3):  # warm
        s.hit()
    n = 3000
    tracemalloc.start()
    base = tracemalloc.take_snapshot()
    for _ in range(n):
        s.hit()
    snap = tracemalloc.take_snapshot()
    tracemalloc.stop()
    grew = sum(
        st.size_diff for st in snap.compare_to(base, "filename")
        if st.traceback[0].filename.endswith("faults.py")
        and st.size_diff > 0)
    assert grew < n, f"disarmed Site.hit allocated {grew}B over {n} hits"
