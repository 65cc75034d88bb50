"""Bench-trajectory regression gate (bench_regress.py): fixture-row
checks, tolerance semantics and the CLI exit contract."""

import json

import pytest

import bench_regress


def _row(value_mean, metric="transformer_base_train_tokens_per_sec",
         unit="tokens/sec", **extra):
    row = {"metric": metric, "value": value_mean * 1.01,
           "value_mean": value_mean, "unit": unit}
    row.update(extra)
    return row


def _driver(parsed, n=1):
    return {"n": n, "cmd": "python bench.py", "rc": 0, "parsed": parsed}


def test_flatten_row_headline_nested_and_metrics_skipped():
    parsed = _row(
        100.0,
        resnet50={"metric": "resnet50_train_images_per_sec",
                  "value": 50.0, "unit": "images/sec"},
        warm_start={"metric": "warm_start_ratio", "value": 0.8,
                    "unit": "ratio"},
        metrics={"pt_executor_steps_total": {"metric": "not_a_row",
                                             "value": 1}},
    )
    flat = bench_regress.flatten_row(parsed)
    assert flat["transformer_base_train_tokens_per_sec"]["value"] == 100.0
    # value_mean preferred over value; nested rows without one fall back
    assert flat["resnet50_train_images_per_sec"]["value"] == 50.0
    assert flat["warm_start_ratio"]["unit"] == "ratio"
    assert "not_a_row" not in flat  # the registry snapshot never gates


def test_check_flags_twenty_percent_drop_and_passes_within_tolerance():
    history = [("r01", bench_regress.flatten_row(_row(90.0))),
               ("r02", bench_regress.flatten_row(_row(100.0)))]
    # 20% below the trailing best (100) -> regression
    (f,) = bench_regress.check(
        bench_regress.flatten_row(_row(80.0)), history)
    assert f["metric"] == "transformer_base_train_tokens_per_sec"
    assert f["best"] == 100.0 and f["best_round"] == "r02"
    assert f["ratio"] == pytest.approx(0.8)
    # 5% below: inside the 10% tolerance
    assert bench_regress.check(
        bench_regress.flatten_row(_row(95.0)), history) == []
    # improvements obviously pass
    assert bench_regress.check(
        bench_regress.flatten_row(_row(120.0)), history) == []


def test_check_per_family_tolerance_and_ungated_units():
    history = [("r01", {
        "fam_tokens_per_sec": {"value": 100.0, "unit": "tokens/sec"},
        "warm_start_seconds": {"value": 10.0, "unit": "seconds"},
    })]
    fresh = {
        "fam_tokens_per_sec": {"value": 75.0, "unit": "tokens/sec"},
        # lower-is-better rider got WORSE but its unit is not gated
        "warm_start_seconds": {"value": 50.0, "unit": "seconds"},
        # brand-new family: no history, never gates
        "decode_tokens_per_sec": {"value": 1.0, "unit": "tokens/sec"},
    }
    (f,) = bench_regress.check(fresh, history)
    assert f["metric"] == "fam_tokens_per_sec"
    # a per-family override wider than the drop silences it
    bench_regress.FAMILY_TOLERANCE["fam_tokens_per_sec"] = 0.30
    try:
        assert bench_regress.check(fresh, history) == []
    finally:
        bench_regress.FAMILY_TOLERANCE.pop("fam_tokens_per_sec")
    # the global tolerance argument works the same way
    assert bench_regress.check(fresh, history, tolerance=0.30) == []


def test_check_flags_family_missing_from_fresh_row():
    """A family whose bench subprocess crashed produces NO metric —
    the worst regression must not pass by absence. The baseline is the
    UNION of history rounds (one bad committed round without the
    family must not erode the guarantee); deliberate removals need an
    explicit RETIRED_METRICS entry."""
    history = [
        ("r01", {"old_tokens_per_sec": {"value": 5.0,
                                        "unit": "tokens/sec"},
                 "fam_tokens_per_sec": {"value": 90.0,
                                        "unit": "tokens/sec"}}),
        # r02 (the newest) itself lacks both old_* and crashy_* —
        # carried-by-ANY-round still gates them
        ("r02", {"fam_tokens_per_sec": {"value": 100.0,
                                        "unit": "tokens/sec"}}),
        ("r01b", {"crashy_images_per_sec": {"value": 40.0,
                                            "unit": "images/sec"}}),
    ]
    fresh = {"fam_tokens_per_sec": {"value": 99.0,
                                    "unit": "tokens/sec"}}
    found = {f["metric"]: f for f in bench_regress.check(fresh, history)}
    assert set(found) == {"old_tokens_per_sec", "crashy_images_per_sec"}
    f = found["crashy_images_per_sec"]
    assert f["missing"] is True and f["value"] is None
    assert f["best"] == 40.0 and f["best_round"] == "r01b"
    # a deliberate retirement is an explicit escape, not silence
    old = bench_regress.RETIRED_METRICS
    bench_regress.RETIRED_METRICS = frozenset({"old_tokens_per_sec"})
    try:
        (f2,) = bench_regress.check(fresh, history)
        assert f2["metric"] == "crashy_images_per_sec"
    finally:
        bench_regress.RETIRED_METRICS = old
    # present again -> no finding
    fresh["crashy_images_per_sec"] = {"value": 41.0,
                                      "unit": "images/sec"}
    fresh["old_tokens_per_sec"] = {"value": 6.0, "unit": "tokens/sec"}
    assert bench_regress.check(fresh, history) == []


def _write_rounds(tmp_path, values):
    paths = []
    for i, v in enumerate(values, start=1):
        p = tmp_path / f"BENCH_r{i:02d}.json"
        p.write_text(json.dumps(_driver(_row(v), n=i)))
        paths.append(str(p))
    return paths


def test_main_exits_nonzero_on_synthetic_drop(tmp_path, capsys):
    _write_rounds(tmp_path, [90.0, 100.0, 79.0])  # fresh = 79 vs best 100
    rc = bench_regress.main(
        ["--history", str(tmp_path / "BENCH_r*.json")])
    assert rc == 1
    out = json.loads(capsys.readouterr().out)
    assert out["ok"] is False and out["row"] == "BENCH_r03.json"
    (f,) = out["regressions"]
    assert f["ratio"] == pytest.approx(0.79)


def test_main_passes_on_healthy_trajectory(tmp_path, capsys):
    _write_rounds(tmp_path, [90.0, 100.0, 97.0])
    rc = bench_regress.main(
        ["--history", str(tmp_path / "BENCH_r*.json")])
    assert rc == 0
    assert json.loads(capsys.readouterr().out)["ok"] is True


def test_main_row_mode_gates_fresh_row_against_all_rounds(tmp_path,
                                                          capsys):
    _write_rounds(tmp_path, [90.0, 100.0])
    fresh = tmp_path / "fresh.json"
    fresh.write_text(json.dumps(_row(70.0)))  # bare row, no wrapper
    rc = bench_regress.main(
        ["--history", str(tmp_path / "BENCH_r*.json"),
         "--row", str(fresh)])
    assert rc == 1
    out = json.loads(capsys.readouterr().out)
    assert out["row"] == "fresh.json"
    assert out["rounds"] == ["BENCH_r01.json", "BENCH_r02.json"]
    # the same fresh row passes once the tolerance covers the gap
    rc = bench_regress.main(
        ["--history", str(tmp_path / "BENCH_r*.json"),
         "--row", str(fresh), "--tolerance", "0.5"])
    assert rc == 0
    capsys.readouterr()


def test_main_needs_enough_history(tmp_path, capsys):
    _write_rounds(tmp_path, [100.0])
    rc = bench_regress.main(
        ["--history", str(tmp_path / "BENCH_r*.json")])
    assert rc == 2
    capsys.readouterr()


def test_degraded_serving_family_gates_with_wide_tolerance():
    """The bench_serving degraded-mode rider (tokens/s under a seeded
    serve.decode delay fault at 1% of steps) is a gated family: a 30%
    drop is flagged under its 0.20 tolerance, a 15% drop is inside it —
    resilience overhead is tracked, not guessed."""
    assert bench_regress.FAMILY_TOLERANCE[
        "serving_degraded_tokens_per_sec"] == pytest.approx(0.20)
    base = _row(
        5000.0, metric="serving_decode_tokens_per_sec",
        degraded={"metric": "serving_degraded_tokens_per_sec",
                  "value": 4000.0, "unit": "tokens/sec",
                  "token_ms_p99": 2.0})
    flat = bench_regress.flatten_row(base)
    assert flat["serving_degraded_tokens_per_sec"]["value"] == 4000.0
    history = [("r06", flat)]

    def fresh(v):
        return bench_regress.flatten_row(_row(
            5000.0, metric="serving_decode_tokens_per_sec",
            degraded={"metric": "serving_degraded_tokens_per_sec",
                      "value": v, "unit": "tokens/sec"}))

    (f,) = bench_regress.check(fresh(2800.0), history)  # -30%
    assert f["metric"] == "serving_degraded_tokens_per_sec"
    assert f["tolerance"] == pytest.approx(0.20)
    assert bench_regress.check(fresh(3400.0), history) == []  # -15%
    # a crashed degraded sweep (row absent) is itself a finding
    missing = bench_regress.flatten_row(_row(
        5000.0, metric="serving_decode_tokens_per_sec"))
    (f,) = bench_regress.check(missing, history)
    assert f["metric"] == "serving_degraded_tokens_per_sec"
    assert f.get("missing") is True


def test_serving_latency_riders_gate_lower_is_better():
    """The serving TTFT/queue-wait p95 riders (bench_serving.py's
    ``latency`` block) gate in the OPPOSITE direction: best is the
    MINIMUM across history, and a fresh value rising more than the
    allowlist tolerance above it is a regression. A plain ``ms`` unit
    outside the allowlist still never gates."""
    assert bench_regress.LATENCY_TOLERANCE[
        "serving_ttft_ms_p95"] == pytest.approx(0.50)

    def row(ttft, qwait):
        return bench_regress.flatten_row(_row(
            5000.0, metric="serving_decode_tokens_per_sec",
            latency={
                "ttft": {"metric": "serving_ttft_ms_p95",
                         "value": ttft, "unit": "ms"},
                "qwait": {"metric": "serving_queue_wait_ms_p95",
                          "value": qwait, "unit": "ms"},
            }))

    history = [("r06", row(100.0, 40.0)), ("r07", row(80.0, 50.0))]
    # best = min across history (80 / 40); +50% boundaries 120 / 60
    found = {f["metric"]: f
             for f in bench_regress.check(row(130.0, 70.0), history)}
    assert set(found) == {"serving_ttft_ms_p95",
                          "serving_queue_wait_ms_p95"}
    f = found["serving_ttft_ms_p95"]
    assert f["direction"] == "above"
    assert f["best"] == 80.0 and f["best_round"] == "r07"
    assert f["ratio"] == pytest.approx(130.0 / 80.0)
    # inside the envelope (and improvements) pass
    assert bench_regress.check(row(115.0, 55.0), history) == []
    assert bench_regress.check(row(10.0, 5.0), history) == []
    # carried-by-history latency rows missing from fresh are findings
    bare = bench_regress.flatten_row(_row(
        5000.0, metric="serving_decode_tokens_per_sec"))
    found = {f["metric"]: f for f in bench_regress.check(bare, history)}
    assert found["serving_ttft_ms_p95"]["missing"] is True
    assert found["serving_ttft_ms_p95"]["tolerance"] == pytest.approx(0.50)
    # an un-allowlisted ms rider never gates, even when it balloons
    hist2 = [("r01", {"tile_ms_p95": {"value": 1.0, "unit": "ms"}})]
    assert bench_regress.check(
        {"tile_ms_p95": {"value": 99.0, "unit": "ms"}}, hist2) == []
