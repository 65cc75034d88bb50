"""The family's optional second check (perf/kinds/train.check_second):
absent, a run's records are what they were; present, it can only make
a run incorrect, never correct. The check here is the kind a language
model's family would bring: the logits of the sample's last position
against the plain reference's, which a loss averaged over hundreds of
positions does not resolve."""

import jax.numpy as jnp
import numpy as np
import pytest

from perf import harness, models
from perf.kinds import train
from perf.reference import transformer as ref

import perfbench_tiny as tiny

LINE_KEYS = {"correct", "attempted", "failed", "metrics", "device",
             "problems"}
LOSS_KEYS = {"program_loss", "reference_loss", "rel"}


def last_logits_check(tol):
    def second_check(w, cfg, sample, fetched):
        src_pad = jnp.asarray(sample["src_pad_mask"])
        enc = ref.encode(w, cfg, jnp.asarray(sample["src_ids"]), src_pad)
        want = np.asarray(ref.decode_logits(
            w, cfg, enc, src_pad, jnp.asarray(sample["trg_ids"]),
            jnp.asarray(sample["trg_pad_mask"])))[:, -1]
        got = np.asarray(fetched["logits"], np.float32)[:, -1]
        err = float(np.abs(got - want).max() / np.sqrt(np.mean(want ** 2)))
        return ([f"last-position logits differ from the reference's by "
                 f"{err:.3g} of their rms > {tol}"] if err > tol else [],
                {"last_logits_err_over_rms": err, "tol": tol})
    return second_check


def run_tiny(cell_name="tbase-train"):
    cell = tiny.train_cell(cell_name)
    run = tiny.make_run(cell, tiny.config(cell["config"]), seconds=0.2)
    train.run(run)
    return run, harness.result_line(run)


@pytest.mark.parametrize("cell_name", [c for c, n in tiny.cells_of("train")
                                       if n == 1])
def test_a_family_without_one_keeps_todays_records(cell_name, capsys):
    run, line = run_tiny(cell_name)
    if hasattr(models.reference(run.config), "second_check"):
        pytest.skip("this family brings a second check")
    assert set(run.check) == LOSS_KEYS and set(line) == LINE_KEYS
    assert set(run.first_calls) == {"startup", "eval_sample", "train_step"}
    assert "second check" not in capsys.readouterr().out


@pytest.mark.parametrize("tol,correct", [(1.0, True), (0.0, False)])
def test_a_second_check_can_only_make_a_run_incorrect(
        monkeypatch, capsys, tol, correct):
    monkeypatch.setattr(models.family({"family": "transformer"}),
                        "CHECK_FETCH", ("logits",), raising=False)
    monkeypatch.setattr(ref, "second_check", last_logits_check(tol),
                        raising=False)
    run, line = run_tiny()
    # the loss check stands as it was; the second adds only its dict
    assert set(run.check) == LOSS_KEYS | {"second"}
    assert run.check["rel"] <= train.LOSS_REL_TOL
    assert set(run.check["second"]) == {"last_logits_err_over_rms", "tol"}
    assert 0 < run.check["second"]["last_logits_err_over_rms"] < 1.0
    assert "eval_second" in run.first_calls        # set-up, not the window
    assert run.compiles_in_window == 0
    assert set(line) == LINE_KEYS and line["correct"] is correct
    assert [p.startswith("last-position logits differ")
            for p in line["problems"]] == ([] if correct else [True])
    out = capsys.readouterr().out
    assert "second check: {'last_logits_err_over_rms'" in out
    assert ("PROBLEM: last-position logits" in out) is not correct


def test_a_second_check_cannot_mend_a_failed_loss_check(monkeypatch):
    monkeypatch.setattr(models.family({"family": "transformer"}),
                        "CHECK_FETCH", ("logits",), raising=False)
    monkeypatch.setattr(ref, "second_check", last_logits_check(1.0),
                        raising=False)
    monkeypatch.setattr(train, "LOSS_REL_TOL", 0.0)
    run, line = run_tiny()
    assert line["correct"] is False and len(line["problems"]) == 1
    assert line["problems"][0].startswith("eval loss")


def test_what_is_fetched_keeps_the_shape_the_family_gave(monkeypatch):
    # a key may hold a list of variables (an index per layer): the
    # reference gets a list of arrays back under the same key
    seen = {}

    def second_check(w, cfg, sample, fetched):
        seen.update(fetched)
        return [], {}

    cfg = tiny.config("transformer-base")
    fam = models.family(cfg)
    real = fam.build_graph

    def build_graph(pcfg, is_test=False):
        model = real(pcfg, is_test=is_test)
        model["pair"] = [model["logits"], model["token_count"]]
        return model

    monkeypatch.setattr(fam, "build_graph", build_graph)
    monkeypatch.setattr(fam, "CHECK_FETCH", ("pair", "loss"), raising=False)
    monkeypatch.setattr(ref, "second_check", second_check, raising=False)
    run, _ = run_tiny()
    assert set(seen) == {"pair", "loss"} and len(seen["pair"]) == 2
    assert seen["pair"][0].shape[:2] == (8, 16)
    assert float(seen["loss"]) == pytest.approx(run.check["program_loss"])
    assert run.check["second"] == {} and run.correct
