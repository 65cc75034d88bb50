"""OpTest coverage for the round-2 op-breadth tranche: sequence ops,
activations, pairwise losses, tensor/vision/detection ops
(reference harness pattern: tests/unittests/test_*_op.py)."""

import numpy as np
import pytest

from tests.op_test import OpHarness

RS = np.random.RandomState


# --- sequence ops (padded + Length semantics) ---


def test_sequence_pad_unpad():
    x = RS(0).randn(2, 4, 3)
    ln = np.array([3, 2], np.int64)
    h = OpHarness("sequence_pad", {"X": x, "Length": ln},
                  out_slots=("Out",))
    exp = x.copy()
    exp[0, 3:] = 0
    exp[1, 2:] = 0
    h.check_output({"Out": exp})
    h.check_grad(["x_0"])

    h2 = OpHarness("sequence_unpad", {"X": x, "Length": ln},
                   out_slots=("Out",))
    h2.check_output({"Out": exp})


def test_sequence_concat():
    a = RS(1).randn(2, 3)
    b = RS(2).randn(2, 4)
    la = np.array([2, 3], np.int64)
    lb = np.array([4, 1], np.int64)
    h = OpHarness(
        "sequence_concat",
        {"X": [a, b], "Length": [la, lb]},
        out_slots=("Out",),
        multi_input_slots=("X", "Length"),
    )
    exp = np.zeros((2, 7))
    exp[0, :2] = a[0, :2]
    exp[0, 2:6] = b[0, :4]
    exp[1, :3] = a[1, :3]
    exp[1, 3:4] = b[1, :1]
    h.check_output({"Out": exp})


def test_sequence_slice():
    x = RS(3).randn(2, 5, 2)
    off = np.array([1, 0], np.int64)
    ln = np.array([3, 2], np.int64)
    h = OpHarness("sequence_slice",
                  {"X": x, "Offset": off, "Length": ln}, out_slots=("Out",))
    exp = np.zeros_like(x)
    exp[0, :3] = x[0, 1:4]
    exp[1, :2] = x[1, 0:2]
    h.check_output({"Out": exp})
    h.check_grad(["x_0"])


def test_sequence_erase():
    x = np.array([[2, 0, 2, 5, 9], [3, 3, 3, 1, 0]], np.int64)
    ln = np.array([5, 4], np.int64)
    h = OpHarness("sequence_erase", {"X": x, "Length": ln},
                  attrs={"tokens": [2, 3]}, out_slots=("Out",))
    exp = np.array([[0, 5, 9, 0, 0], [1, 0, 0, 0, 0]], np.int64)
    h.check_output({"Out": exp})


def test_sequence_enumerate():
    x = np.array([[1, 2, 3, 4]], np.int64)
    ln = np.array([3], np.int64)
    h = OpHarness("sequence_enumerate", {"X": x, "Length": ln},
                  attrs={"win_size": 2, "pad_value": 0},
                  out_slots=("Out",))
    exp = np.array([[[1, 2], [2, 3], [3, 0], [0, 0]]], np.int64)
    h.check_output({"Out": exp})


def test_sequence_expand_as():
    x = RS(4).randn(2, 3)
    y = RS(5).randn(2, 4, 3)
    ln = np.array([4, 2], np.int64)
    h = OpHarness("sequence_expand_as",
                  {"X": x, "Y": y, "Length": ln}, out_slots=("Out",))
    exp = np.repeat(x[:, None, :], 4, axis=1)
    exp[1, 2:] = 0
    h.check_output({"Out": exp})
    h.check_grad(["x_0"])


# --- activations ---


@pytest.mark.parametrize("op,fn,attrs", [
    ("tanh_shrink", lambda x: x - np.tanh(x), {}),
    ("softshrink",
     lambda x: np.where(x > 0.5, x - 0.5, np.where(x < -0.5, x + 0.5, 0)),
     {"lambda": 0.5}),
    ("hard_shrink", lambda x: np.where(np.abs(x) > 0.5, x, 0),
     {"threshold": 0.5}),
    ("brelu", lambda x: np.clip(x, 0.1, 0.9),
     {"t_min": 0.1, "t_max": 0.9}),
    ("stanh", lambda x: 1.7159 * np.tanh(0.67 * x), {}),
    ("thresholded_relu", lambda x: np.where(x > 1.0, x, 0),
     {"threshold": 1.0}),
])
def test_new_activations(op, fn, attrs):
    x = RS(6).randn(3, 4) * 2
    h = OpHarness(op, {"X": x}, attrs=attrs)
    h.check_output({"Out": fn(x)})


def test_soft_relu_and_selu_grads():
    x = RS(7).randn(3, 4)
    h = OpHarness("soft_relu", {"X": x})
    h.check_output({"Out": np.log1p(np.exp(np.clip(x, -40, 40)))})
    h.check_grad(["x_0"])
    # keep x away from selu's kink at 0 (finite differences straddle it)
    x_off = x + np.where(x >= 0, 0.5, -0.5)
    h2 = OpHarness("selu", {"X": x_off})
    h2.check_grad(["x_0"])


# --- losses ---


def test_log_loss():
    p = RS(8).uniform(0.05, 0.95, (4, 1))
    y = RS(9).randint(0, 2, (4, 1)).astype(np.float64)
    h = OpHarness("log_loss", {"Predicted": p, "Labels": y},
                  out_slots=("Loss",))
    eps = 1e-4
    exp = -(y * np.log(p + eps) + (1 - y) * np.log(1 - p + eps))
    h.check_output({"Loss": exp})
    h.check_grad(["predicted_0"])


def test_rank_and_margin_rank_loss():
    l_, r_ = RS(10).randn(4, 1), RS(11).randn(4, 1)
    y = RS(12).randint(0, 2, (4, 1)).astype(np.float64)
    h = OpHarness("rank_loss", {"Label": y, "Left": l_, "Right": r_})
    exp = np.logaddexp(0, l_ - r_) - y * (l_ - r_)
    h.check_output({"Out": exp})
    h.check_grad(["left_0", "right_0"])

    y2 = np.where(y > 0, 1.0, -1.0)
    h2 = OpHarness("margin_rank_loss",
                   {"Label": y2, "X1": l_, "X2": r_},
                   attrs={"margin": 0.1})
    exp2 = np.maximum(0, -y2 * (l_ - r_) + 0.1)
    h2.check_output({"Out": exp2})


def test_hinge_kldiv_bpr_cos_sim():
    logits = RS(13).randn(4, 1)
    y = RS(14).randint(0, 2, (4, 1)).astype(np.float64)
    OpHarness("hinge_loss", {"Logits": logits, "Labels": y},
              out_slots=("Loss",)).check_output(
        {"Loss": np.maximum(0, 1 - (2 * y - 1) * logits)})

    x = np.log(RS(15).dirichlet(np.ones(5), 3))
    t = RS(16).dirichlet(np.ones(5), 3)
    h = OpHarness("kldiv_loss", {"X": x, "Target": t},
                  attrs={"reduction": "mean"}, out_slots=("Loss",))
    exp = np.mean(np.where(t > 0, t * (np.log(t) - x), 0.0))
    h.check_output({"Loss": exp})
    h.check_grad(["x_0"])

    scores = RS(17).randn(3, 4)
    label = np.array([[1], [0], [3]], np.int64)
    hb = OpHarness("bpr_loss", {"X": scores, "Label": label},
                   out_slots=("Y",))
    pos = np.take_along_axis(scores, label, 1)
    lo = np.logaddexp(0, -(pos - scores))
    mask = np.zeros_like(scores)
    np.put_along_axis(mask, label, 1.0, 1)
    exp = (lo * (1 - mask)).sum(1, keepdims=True) / 3
    hb.check_output({"Y": exp})
    hb.check_grad(["x_0"])

    a, b = RS(18).randn(3, 5), RS(19).randn(3, 5)
    hc = OpHarness("cos_sim", {"X": a, "Y": b}, out_slots=("Out",))
    exp = (a * b).sum(-1, keepdims=True) / (
        np.linalg.norm(a, axis=-1, keepdims=True)
        * np.linalg.norm(b, axis=-1, keepdims=True))
    hc.check_output({"Out": exp})
    hc.check_grad(["x_0", "y_0"])


# --- tensor / vision ---


def test_reverse_argsort_diag_linspace():
    x = RS(20).randn(3, 4)
    OpHarness("reverse", {"X": x}, attrs={"axis": [1]}).check_output(
        {"Out": x[:, ::-1]})
    h = OpHarness("argsort", {"X": x}, out_slots=("Out", "Indices"))
    h.check_output({"Out": np.sort(x, -1),
                    "Indices": np.argsort(x, -1)})
    d = RS(21).randn(4)
    OpHarness("diag", {"Diagonal": d}).check_output({"Out": np.diag(d)})
    OpHarness("linspace", {
        "Start": np.array([0.0]), "Stop": np.array([1.0])},
        attrs={"num": 5}).check_output(
        {"Out": np.linspace(0, 1, 5)})


def test_gather_scatter_nd():
    x = RS(22).randn(3, 4)
    idx = np.array([[0, 1], [2, 3]], np.int64)
    h = OpHarness("gather_nd", {"X": x, "Index": idx})
    h.check_output({"Out": x[[0, 2], [1, 3]]})
    h.check_grad(["x_0"])

    upd = RS(23).randn(2)
    h2 = OpHarness("scatter_nd_add", {"X": x, "Index": idx, "Updates": upd})
    exp = x.copy()
    exp[0, 1] += upd[0]
    exp[2, 3] += upd[1]
    h2.check_output({"Out": exp})
    h2.check_grad(["x_0", "updates_0"])


def test_pad_crop_family():
    x = RS(24).randn(1, 2, 3, 3)
    h = OpHarness("pad2d", {"X": x},
                  attrs={"paddings": [1, 1, 2, 2], "mode": "constant",
                         "pad_value": 0.5})
    exp = np.pad(x, ((0, 0), (0, 0), (1, 1), (2, 2)), constant_values=0.5)
    h.check_output({"Out": exp})
    h.check_grad(["x_0"])

    big = RS(25).randn(3, 4)
    small = RS(26).randn(2, 3)
    OpHarness("pad_constant_like", {"X": big, "Y": small},
              attrs={"pad_value": 1.0}).check_output(
        {"Out": np.pad(small, ((0, 1), (0, 1)), constant_values=1.0)})

    OpHarness("crop", {"X": big},
              attrs={"offsets": [1, 1], "shape": [2, 2]}).check_output(
        {"Out": big[1:3, 1:3]})


def test_channel_shuffles():
    x = RS(27).randn(1, 4, 2, 2)
    h = OpHarness("shuffle_channel", {"X": x}, attrs={"group": 2})
    exp = x.reshape(1, 2, 2, 2, 2).swapaxes(1, 2).reshape(1, 4, 2, 2)
    h.check_output({"Out": exp})

    x2 = RS(28).randn(1, 4, 2, 2)
    h2 = OpHarness("pixel_shuffle", {"X": x2}, attrs={"upscale_factor": 2})
    ps = np.transpose(x2.reshape(1, 1, 2, 2, 2, 2), (0, 1, 4, 2, 5, 3)
                      ).reshape(1, 1, 4, 4)
    h2.check_output({"Out": ps})
    h2.check_grad(["x_0"])

    # space_to_depth round-trips pixel_shuffle's spatial blocks: its output
    # holds exactly x2's values (block layout permutes the channel order)
    from paddle_tpu.core.registry import get_op_def

    out3 = np.asarray(
        get_op_def("space_to_depth").compute(
            {"X": [ps]}, {"blocksize": 2})["Out"][0]
    )
    assert out3.shape == (1, 4, 2, 2)
    np.testing.assert_allclose(np.sort(out3.ravel()), np.sort(x2.ravel()))


def test_multiplex_and_shard_index():
    a, b = RS(29).randn(3, 2), RS(30).randn(3, 2)
    ids = np.array([[0], [1], [0]], np.int64)
    h = OpHarness("multiplex", {"X": [a, b], "Ids": ids},
                  multi_input_slots=("X",))
    exp = np.stack([a[0], b[1], a[2]])
    h.check_output({"Out": exp})

    x = np.array([[1], [7], [15]], np.int64)
    h2 = OpHarness("shard_index", {"X": x},
                   attrs={"index_num": 16, "nshards": 2, "shard_id": 0,
                          "ignore_value": -1})
    h2.check_output({"Out": np.array([[1], [7], [-1]], np.int64)})


def test_interp_ops():
    x = RS(31).randn(1, 1, 2, 2)
    h = OpHarness("nearest_interp", {"X": x},
                  attrs={"out_h": 4, "out_w": 4, "align_corners": False})
    exp = x.repeat(2, axis=2).repeat(2, axis=3)
    h.check_output({"Out": exp})

    hb = OpHarness("bilinear_interp", {"X": x},
                   attrs={"out_h": 3, "out_w": 3, "align_corners": True})
    ys = np.linspace(0, 1, 3)
    exp2 = np.zeros((1, 1, 3, 3))
    for i, fy in enumerate(ys):
        for j, fx in enumerate(ys):
            y0, x0 = int(np.floor(fy)), int(np.floor(fx))
            y1, x1 = min(y0 + 1, 1), min(x0 + 1, 1)
            wy, wx = fy - y0, fx - x0
            exp2[0, 0, i, j] = (
                x[0, 0, y0, x0] * (1 - wy) * (1 - wx)
                + x[0, 0, y1, x0] * wy * (1 - wx)
                + x[0, 0, y0, x1] * (1 - wy) * wx
                + x[0, 0, y1, x1] * wy * wx)
    hb.check_output({"Out": exp2})
    hb.check_grad(["x_0"])


def test_norm_affine_channel_row_conv():
    x = RS(32).randn(2, 3, 2)
    h = OpHarness("norm", {"X": x}, attrs={"axis": 1}, out_slots=("Out",))
    n = np.sqrt((x * x).sum(1, keepdims=True) + 1e-10)
    h.check_output({"Out": x / n})
    h.check_grad(["x_0"])

    xc = RS(33).randn(2, 3, 2, 2)
    s, b = RS(34).randn(3), RS(35).randn(3)
    h2 = OpHarness("affine_channel", {"X": xc, "Scale": s, "Bias": b})
    h2.check_output(
        {"Out": xc * s.reshape(1, 3, 1, 1) + b.reshape(1, 3, 1, 1)})
    h2.check_grad(["x_0", "scale_0", "bias_0"])

    xt = RS(36).randn(2, 5, 3)
    f = RS(37).randn(2, 3)
    h3 = OpHarness("row_conv", {"X": xt, "Filter": f})
    xp = np.pad(xt, ((0, 0), (0, 1), (0, 0)))
    exp = xp[:, 0:5] * f[0] + xp[:, 1:6] * f[1]
    h3.check_output({"Out": exp})
    h3.check_grad(["x_0", "filter_0"])


def test_iou_similarity_and_box_coder():
    x = np.array([[0, 0, 2, 2], [1, 1, 3, 3]], np.float64)
    y = np.array([[1, 1, 2, 2]], np.float64)
    h = OpHarness("iou_similarity", {"X": x, "Y": y})
    h.check_output({"Out": np.array([[1.0 / 4.0], [1.0 / 4.0]])})

    prior = np.array([[0.0, 0.0, 1.0, 1.0]], np.float64)
    target = np.array([[0.25, 0.25, 0.75, 0.75]], np.float64)
    he = OpHarness("box_coder", {"PriorBox": prior, "TargetBox": target},
                   attrs={"code_type": "encode_center_size"},
                   out_slots=("OutputBox",))
    # center offsets 0, log size ratio log(0.5)
    exp = np.array([[[0.0, 0.0, np.log(0.5), np.log(0.5)]]])
    he.check_output({"OutputBox": exp})

    code = exp
    hd = OpHarness("box_coder", {"PriorBox": prior, "TargetBox": code},
                   attrs={"code_type": "decode_center_size"},
                   out_slots=("OutputBox",))
    hd.check_output({"OutputBox": target[None, :, :].transpose(1, 0, 2)})


def test_sync_batch_norm_alias():
    x = RS(38).randn(4, 3, 2, 2)
    scale, bias = np.ones(3), np.zeros(3)
    mean, var = np.zeros(3), np.ones(3)
    h = OpHarness(
        "sync_batch_norm",
        {"X": x, "Scale": scale, "Bias": bias, "Mean": mean,
         "Variance": var},
        attrs={"is_test": False}, out_slots=("Y",),
    )
    mu = x.mean(axis=(0, 2, 3))
    v = x.var(axis=(0, 2, 3))
    exp = (x - mu.reshape(1, 3, 1, 1)) / np.sqrt(
        v.reshape(1, 3, 1, 1) + 1e-5)
    h.check_output({"Y": exp})


def test_prior_box_and_anchor_generator_shapes():
    feat = np.zeros((1, 8, 4, 4), np.float32)
    img = np.zeros((1, 3, 64, 64), np.float32)
    h = OpHarness("prior_box", {"Input": feat, "Image": img},
                  attrs={"min_sizes": [16.0], "aspect_ratios": [2.0],
                         "flip": True, "clip": True},
                  out_slots=("Boxes", "Variances"))
    main_out = h  # shapes checked through check_output with computed exp?
    # 1 min_size x (1 + 2 flipped ratios) = 3 priors per cell
    import jax

    jax.config.update("jax_platforms", "cpu")
    from paddle_tpu.core.registry import get_op_def

    outs = get_op_def("prior_box").compute(
        {"Input": [feat], "Image": [img]},
        {"min_sizes": [16.0], "aspect_ratios": [2.0], "flip": True,
         "clip": True})
    assert outs["Boxes"][0].shape == (4, 4, 3, 4)
    assert outs["Variances"][0].shape == (4, 4, 3, 4)
    assert (np.asarray(outs["Boxes"][0]) >= 0).all()

    outs2 = get_op_def("anchor_generator").compute(
        {"Input": [feat]},
        {"anchor_sizes": [32.0], "aspect_ratios": [1.0],
         "stride": [16.0, 16.0]})
    assert outs2["Anchors"][0].shape == (4, 4, 1, 4)
    a = np.asarray(outs2["Anchors"][0])
    np.testing.assert_allclose(a[0, 0, 0], [8 - 16, 8 - 16, 8 + 16, 8 + 16])


def test_nearest_interp_mixed_axes_align_corners():
    """align_corners must apply independently per axis (code-review
    finding, round 2: out_h==1 must not disable width alignment)."""
    x = RS(40).randn(1, 1, 1, 4)
    h = OpHarness("nearest_interp", {"X": x},
                  attrs={"out_h": 1, "out_w": 7, "align_corners": True})
    xs = np.round(np.linspace(0, 3, 7)).astype(int)
    h.check_output({"Out": x[:, :, :, xs]})


def test_grid_sampler_zero_pads_out_of_bounds():
    x = np.ones((1, 1, 2, 2))
    grid = np.full((1, 1, 1, 2), -5.0)  # all 4 corners out of bounds
    from paddle_tpu.core.registry import get_op_def

    out = np.asarray(get_op_def("grid_sampler").compute(
        {"X": [x], "Grid": [grid]}, {})["Output"][0])
    np.testing.assert_allclose(out, 0.0)

    # half-a-pixel outside: only the in-bounds corner contributes (0.25)
    grid2 = np.full((1, 1, 1, 2), -2.0)
    out2 = np.asarray(get_op_def("grid_sampler").compute(
        {"X": [x], "Grid": [grid2]}, {})["Output"][0])
    np.testing.assert_allclose(out2, 0.25)


def test_sequence_pad_vector_pad_value():
    x = RS(41).randn(2, 3, 2)
    ln = np.array([2, 1], np.int64)
    pv = np.array([7.0, -7.0])
    h = OpHarness("sequence_pad", {"X": x, "PadValue": pv, "Length": ln},
                  out_slots=("Out",))
    exp = x.copy()
    exp[0, 2:] = pv
    exp[1, 1:] = pv
    h.check_output({"Out": exp})


def test_prior_box_max_size_index_pairing():
    """max_sizes pair index-wise with min_sizes (code-review finding,
    round 2): 2 min x (1+2 ars) + 2 paired max = 8 priors, not 10."""
    from paddle_tpu.core.registry import get_op_def

    feat = np.zeros((1, 8, 2, 2), np.float32)
    img = np.zeros((1, 3, 32, 32), np.float32)
    outs = get_op_def("prior_box").compute(
        {"Input": [feat], "Image": [img]},
        {"min_sizes": [30.0, 60.0], "max_sizes": [60.0, 111.0],
         "aspect_ratios": [2.0], "flip": True, "clip": False})
    assert outs["Boxes"][0].shape == (2, 2, 8, 4)


def test_box_coder_variances_roundtrip():
    prior = np.array([[0.0, 0.0, 1.0, 1.0]], np.float64)
    target = np.array([[0.25, 0.25, 0.75, 0.75]], np.float64)
    var = [0.1, 0.1, 0.2, 0.2]
    from paddle_tpu.core.registry import get_op_def

    enc = np.asarray(get_op_def("box_coder").compute(
        {"PriorBox": [prior], "TargetBox": [target]},
        {"code_type": "encode_center_size", "variance": var})["OutputBox"][0])
    np.testing.assert_allclose(
        enc[0, 0], [0.0, 0.0, np.log(0.5) / 0.2, np.log(0.5) / 0.2])
    dec = np.asarray(get_op_def("box_coder").compute(
        {"PriorBox": [prior], "TargetBox": [enc]},
        {"code_type": "decode_center_size", "variance": var})["OutputBox"][0])
    np.testing.assert_allclose(dec[0, 0], target[0], atol=1e-12)


def test_sequence_pad_2d_with_unit_pad_value():
    x = np.array([[5, 6, 7], [8, 9, 1]], np.float64)
    ln = np.array([2, 1], np.int64)
    h = OpHarness("sequence_pad",
                  {"X": x, "PadValue": np.array([0.5]), "Length": ln},
                  out_slots=("Out",))
    exp = x.copy()
    exp[0, 2:] = 0.5
    exp[1, 1:] = 0.5
    h.check_output({"Out": exp})


def test_interp_scale_attr():
    from paddle_tpu.core.registry import get_op_def

    x = RS(44).randn(1, 1, 2, 2)
    out = np.asarray(get_op_def("nearest_interp").compute(
        {"X": [x]}, {"scale": 2.0, "align_corners": False})["Out"][0])
    np.testing.assert_allclose(out, x.repeat(2, 2).repeat(2, 3))


def test_multiprocess_reader_interleaves_all_samples():
    """reference: decorator.py multiprocess_reader — one process per
    reader, all samples delivered."""
    from paddle_tpu.reader import decorator

    def make(lo, hi):
        def r():
            for i in range(lo, hi):
                yield (i, np.arange(3) + i)
        return r

    mr = decorator.multiprocess_reader([make(0, 20), make(100, 120)])
    got = sorted(s[0] for s in mr())
    assert got == list(range(0, 20)) + list(range(100, 120))

    with pytest.raises(ValueError):
        decorator.multiprocess_reader([])


def test_multiprocess_reader_ndarray_samples_and_errors(monkeypatch):
    """Bare ndarray samples work, worker exceptions surface, a killed
    worker is an error while its sibling lives, and early exit doesn't
    stall (code-review findings, round 2)."""
    import os
    import signal
    import time

    from paddle_tpu.reader import decorator

    def arr_reader():
        for i in range(5):
            yield np.arange(3) + i  # bare ndarray payload

    got = list(decorator.multiprocess_reader([arr_reader])())
    assert len(got) == 5

    def bad_reader():
        yield np.zeros(2)
        raise RuntimeError("boom")

    with pytest.raises(RuntimeError, match="worker failed"):
        list(decorator.multiprocess_reader([bad_reader])())

    # a child killed before its end beside one that lives: the error the
    # docstring promises, two polls after the death and not when the
    # sleeper ends (30 s)
    def killed_reader():
        yield np.zeros(2)
        os.kill(os.getpid(), signal.SIGKILL)

    def sleeping_reader():
        time.sleep(30.0)
        yield np.zeros(2)

    monkeypatch.setattr(decorator, "_POLL_S", 0.5)
    t0 = time.perf_counter()
    with pytest.raises(RuntimeError, match="process 0 of 2 died without "
                                           "an end/error.*code -9"):
        list(decorator.multiprocess_reader([killed_reader,
                                            sleeping_reader])())
    assert time.perf_counter() - t0 < 5.0

    def big_reader():
        for i in range(100000):
            yield np.zeros(16)

    t0 = time.perf_counter()
    it = decorator.multiprocess_reader([big_reader, big_reader],
                                       queue_size=8)()
    for _, _s in zip(range(3), it):
        pass
    it.close()  # early exit must terminate workers promptly
    assert time.perf_counter() - t0 < 5.0


def test_bilinear_tensor_product_op():
    x = RS(50).randn(3, 4)
    y = RS(51).randn(3, 5)
    w = RS(52).randn(2, 4, 5)
    b = RS(53).randn(2)
    h = OpHarness("bilinear_tensor_product",
                  {"X": x, "Y": y, "Weight": w, "Bias": b})
    exp = np.einsum("bi,kij,bj->bk", x, w, y) + b[None, :]
    h.check_output({"Out": exp})
    h.check_grad(["x_0", "y_0", "weight_0", "bias_0"])


def test_nce_layer_trains():
    import paddle_tpu as fluid
    from paddle_tpu import layers

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data("x", shape=[16], dtype="float32")
        label = layers.data("label", shape=[1], dtype="int64")
        emb = layers.fc(x, 24, act="relu",
                        param_attr=fluid.ParamAttr(name="nce_h.w"))
        cost = layers.nce(emb, label, num_total_classes=50,
                          num_neg_samples=8,
                          param_attr=fluid.ParamAttr(name="nce.w"))
        loss = layers.mean(cost)
        fluid.optimizer.Adam(5e-3).minimize(loss)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    rng = RS(0)
    probe = RS(1).randn(16, 50)
    losses = []
    with fluid.scope_guard(scope):
        exe.run(startup)
        for _ in range(40):
            xv = rng.randn(64, 16).astype(np.float32)
            yv = np.argmax(xv @ probe, 1).astype(np.int64)[:, None]
            losses.append(float(
                exe.run(main, feed={"x": xv, "label": yv},
                        fetch_list=[loss])[0]))
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0] * 0.8  # NCE cost decreasing
