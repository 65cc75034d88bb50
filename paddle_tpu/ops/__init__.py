"""Operator library.

TPU-native kernel set covering the reference's operator library
(reference: paddle/fluid/operators/ — 415 REGISTER_OPERATOR sites). Every
kernel is a pure JAX function; XLA fuses, tiles onto the MXU, and schedules.
Grad kernels are auto-derived (core/autodiff.py) unless an op registers a
custom grad maker.
"""

from paddle_tpu.ops import (  # noqa: F401
    activation_ops,
    attention_ops,
    control_flow_ops,
    crf_ops,
    decode_ops,
    detection_ops,
    dsa_ops,
    hc_ops,
    linear_attention_ops,
    mamba2_scan_ops,
    math_ops,
    metric_ops,
    misc_ops,
    moe_ops,
    nn_ops,
    optimizer_ops,
    quant_ops,
    rnn_ops,
    selective_scan_ops,
    sequence_ops,
    serving_ops,
    sparse_ops,
    tensor_ops,
    vision_ops,
)
