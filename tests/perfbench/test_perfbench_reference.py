"""Each plain reference against the program at a tiny config, and the
tolerance against the terms whose loss it must notice."""

import jax
import numpy as np
import pytest

import paddle_tpu as fluid
from perf import models
from perf.kinds.train import LOSS_REL_TOL
from perf.reference.common import weights_from_scope

import perfbench_tiny as tiny


def eval_loss_pair(name, cfg=None, ref_cfg=None, amp=True):
    """(program's eval-mode loss, reference's) on two sequences of the
    configuration's first train cell, cut down."""
    cfg = cfg or tiny.config(name)
    main, startup, evalp, loss, _ = models.build_train(cfg, seed=11)
    evalp._amp = amp
    scope, exe = fluid.Scope(), fluid.Executor()
    exe.run(startup, scope=scope)
    fam = models.family(cfg)
    feed = fam.feeds(cfg, dict(tiny.train_cell_of(name)["traffic"],
                               batch=2, feeds=1), 5)[0]
    got = float(np.asarray(exe.run(evalp, feed=feed, fetch_list=[loss],
                                   scope=scope)[0]))
    with jax.default_matmul_precision("highest"):
        want = float(models.reference(cfg).loss(
            weights_from_scope(scope), ref_cfg or cfg, feed))
    return got, want


# every configuration of BENCHMARK.json, a later PR's too
@pytest.mark.parametrize("name", tiny.CONFIGS)
def test_reference_agrees_with_the_eval_program(name):
    # in float32 the two are the same mathematics
    got, want = eval_loss_pair(name, amp=False)
    assert np.isfinite(got)
    assert abs(got - want) / want < 2e-5
    # bf16 matmuls at a width of 32 round far more coarsely than at 512
    # or 768, where the chip's sample agrees to 8e-7..2e-4 (PERF.md)
    got, want = eval_loss_pair(name)
    assert abs(got - want) / want < 2e-3


def test_tolerance_notices_a_dropped_label_smoothing_term():
    cfg = tiny.config("transformer-base")
    got, without = eval_loss_pair("transformer-base", cfg,
                                  dict(cfg, label_smooth_eps=0.0))
    assert abs(got - without) / without > 2 * LOSS_REL_TOL


def test_tolerance_notices_a_dropped_layer():
    cfg = tiny.config("bert-base")
    got, shallow = eval_loss_pair("bert-base", cfg, dict(cfg, n_layer=1))
    print("dropped layer moves the loss by", abs(got - shallow) / shallow)
    assert abs(got - shallow) / shallow > 2 * LOSS_REL_TOL


def test_greedy_logits_rows_do_not_see_the_future():
    cfg = tiny.config("transformer-base")
    _, scope = models.build_serve_weights(cfg, seed=3)
    w = weights_from_scope(scope)
    ref = models.reference(cfg)
    src = np.arange(3, 12)
    a = np.asarray(ref.greedy_logits(w, cfg, src, [4, 9, 7, 5]))
    b = np.asarray(ref.greedy_logits(w, cfg, src, [4, 9, 8, 6]))
    assert a.shape == (4, cfg["trg_vocab_size"])
    np.testing.assert_allclose(a[:3], b[:3], rtol=1e-5, atol=1e-6)
    assert not np.allclose(a[3], b[3])
