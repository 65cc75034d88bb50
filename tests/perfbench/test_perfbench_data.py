"""The traffic generators: a seed repeats, seeds differ, and every seed
gets the same multiset of sizes and gaps in another order."""

import numpy as np
import pytest

from perf import data, models

import perfbench_tiny as tiny

BIG_SEED = 2 ** 31 + 12345  # the driver's seeds pass 32 signed bits


@pytest.mark.parametrize("name", tiny.CONFIGS)
def test_train_feeds_repeat_for_a_seed_and_differ_across_seeds(name):
    cfg = tiny.config(name)
    traffic = dict(tiny.train_cell_of(name)["traffic"], feeds=3)
    fam = models.family(cfg)
    a = fam.feeds(cfg, traffic, BIG_SEED)
    b = fam.feeds(cfg, traffic, BIG_SEED)
    c = fam.feeds(cfg, traffic, BIG_SEED + 1)
    assert len(a) == 3
    for fa, fb in zip(a, b):
        for k in fa:
            np.testing.assert_array_equal(fa[k], fb[k])
    assert any(not np.array_equal(fa[k], fc[k])
               for fa, fc in zip(a, c) for k in fa)


def test_every_seed_trains_the_same_number_of_real_tokens():
    cfg = tiny.config("transformer-base")
    traffic = {"batch": 8, "seq_len": 16, "real_len": [8, 16], "feeds": 4}
    totals, orders = set(), set()
    for seed in (1, 2, BIG_SEED):
        feeds = models.family(cfg).feeds(cfg, traffic, seed)
        lens = np.concatenate([f["trg_pad_mask"].sum(1) for f in feeds])
        totals.add(int(lens.sum()))
        orders.add(tuple(lens.astype(int)))
        assert sorted(lens.astype(int)) == sorted(
            data.spaced_lengths(32, 8, 16))
    assert len(totals) == 1 and len(orders) == 3


def test_spaced_lengths_are_the_uniform_quantiles():
    got = data.spaced_lengths(512, 128, 256)
    assert got.min() == 128 and got.max() == 256
    assert abs(got.mean() - 192.0) < 0.01
    assert int(got.sum()) * 1 == 98304  # 4 feeds x 128 x 192


def test_bert_feed_masks_fifteen_percent_of_real_positions():
    cfg = tiny.config("bert-base")
    feed = models.family(cfg).feeds(cfg, {"batch": 4, "seq_len": 16,
                                          "real_len": [16, 16],
                                          "feeds": 1}, 3)[0]
    assert feed["pad_mask"].sum() == 4 * 16
    assert ((feed["mlm_labels"] >= 0).sum(1) == 2).all()   # int(16 * .15)
    assert (feed["input_ids"][feed["mlm_labels"] >= 0] == 3).all()


def test_poisson_gaps_and_lognormal_lengths_are_fixed_multisets():
    gaps = data.poisson_gaps(1000, 50.0)
    assert abs(gaps.sum() - 20.0) < 1e-9
    # exponential: the median gap is ln 2 of the mean, the CV is near 1
    assert abs(np.median(gaps) / gaps.mean() - np.log(2)) < 0.01
    assert 0.9 < gaps.std() / gaps.mean() < 1.05
    lens = data.lognormal_lengths(1000, 64, 0.7, 8, 256)
    assert lens.min() >= 8 and lens.max() <= 256
    assert abs(np.median(lens) - 64) <= 1


def test_serve_schedule_repeats_and_permutes():
    cfg = tiny.config("transformer-base")
    traffic = tiny.serve_cell(rate=40.0)["traffic"]
    a = data.serve_requests(cfg, traffic, BIG_SEED, 5.0)
    b = data.serve_requests(cfg, traffic, BIG_SEED, 5.0)
    c = data.serve_requests(cfg, traffic, 7, 5.0)
    assert len(a) == 200 and a[0]["due"] == 0.0 and a[-1]["due"] < 5.0
    assert [r["due"] for r in a] == [r["due"] for r in b]
    assert all(np.array_equal(x["src"], y["src"]) for x, y in zip(a, b))
    assert [r["due"] for r in a] != [r["due"] for r in c]
    # the same work in another order
    assert sorted(len(r["src"]) for r in a) == sorted(
        len(r["src"]) for r in c)
    assert sorted(r["max_new"] for r in a) == sorted(
        r["max_new"] for r in c)
    for r in a:
        want = int(np.ceil(1.1 * len(r["src"])))
        assert r["max_new"] == min(max(want, 2), 23)


def test_bursty_gaps_keep_the_mean_rate():
    cfg = tiny.config("transformer-base")
    traffic = dict(tiny.serve_cell(rate=40.0)["traffic"], gap_cv=3.0)
    reqs = data.serve_requests(cfg, traffic, 5, 10.0)
    due = np.asarray([r["due"] for r in reqs])
    gaps = np.diff(due)
    assert len(reqs) == 400 and due[-1] < 10.0
    assert gaps.std() / gaps.mean() > 2.0


# --- the tests' own cut-down cells and configurations ---------------------


@pytest.mark.parametrize("name", tiny.CONFIGS)
def test_a_configurations_tiny_sizes_are_its_familys(name):
    from perf import harness

    full = harness.load_json("perf", "configs", f"{name}.json")
    cfg, fam = tiny.config(name), models.family(full)
    # TINY shrinks sizes the file has; every other key stays as it is run
    assert fam.TINY and set(fam.TINY) <= set(full)
    assert {k: cfg[k] for k in fam.TINY} == fam.TINY
    assert {k: v for k, v in cfg.items() if k not in fam.TINY} == \
        {k: v for k, v in full.items() if k not in fam.TINY}
    assert fam.program_config(cfg) is not None


@pytest.mark.parametrize("cell_name,chips", tiny.cells_of("train"))
def test_a_tiny_train_cell_keeps_its_other_traffic_keys(cell_name, chips):
    from perf import harness

    full = harness.load_json("perf", "workloads", f"{cell_name}.json")
    cell = tiny.train_cell(cell_name)
    assert cell["chips"] == chips == full["chips"]
    assert tiny.train_cell(cell_name, 1)["chips"] == 1
    cut = {"batch": 8, "seq_len": 16, "feeds": 4}
    assert {k: cell["traffic"][k] for k in cut} == cut
    assert cell["traffic"]["real_len"][1] == 16
    assert {k: v for k, v in cell["traffic"].items()
            if k not in (*cut, "real_len")} == \
        {k: v for k, v in full["traffic"].items()
         if k not in (*cut, "real_len")}
