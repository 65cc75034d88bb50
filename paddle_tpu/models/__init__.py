"""Model zoo (reference: benchmark/fluid/models/ + tests/book models)."""

from paddle_tpu.models import (  # noqa: F401
    bert,
    deepfm,
    mnist,
    olmoe,
    resnet,
    se_resnext,
    seq2seq,
    stacked_lstm,
    transformer,
    vgg,
)
