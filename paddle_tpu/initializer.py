"""Parameter initializers (reference: python/paddle/fluid/initializer.py).

Each initializer appends a fill op on the parameter into the startup
program; RNG is stateless-keyed (see ops/tensor_ops.py).
"""

from __future__ import annotations

import math

import numpy as np


class Initializer:
    def __call__(self, var, block):
        raise NotImplementedError


class ConstantInitializer(Initializer):
    def __init__(self, value: float = 0.0):
        self.value = value

    def __call__(self, var, block):
        block.append_op(
            "fill_constant",
            outputs={"Out": var.name},
            attrs={
                "shape": list(var.shape),
                "dtype": var.dtype,
                "value": float(self.value),
            },
        )


class UniformInitializer(Initializer):
    def __init__(self, low=-1.0, high=1.0, seed=0):
        self.low, self.high, self.seed = low, high, seed

    def __call__(self, var, block):
        block.append_op(
            "uniform_random",
            outputs={"Out": var.name},
            attrs={
                "shape": list(var.shape),
                "dtype": var.dtype,
                "min": float(self.low),
                "max": float(self.high),
                "seed": self.seed,
            },
        )


class LogUniformInitializer(UniformInitializer):
    """log of uniform(low, high), low > 0: a decay rate's logarithm
    (Gated DeltaNet's ``A_log``)."""

    def __call__(self, var, block):
        super().__call__(var, block)
        block.append_op("log", inputs={"X": var.name},
                        outputs={"Out": var.name})


class InverseSoftplusInitializer(UniformInitializer):
    """softplus^-1 of dt, log dt ~ uniform(log low, log high): a step
    size's bias (Kimi Delta Attention's ``dt_bias``, Mamba's), so that
    softplus(bias) starts log-uniform in [low, high]."""

    def __init__(self, low, high, seed=0):
        super().__init__(math.log(low), math.log(high), seed)

    def __call__(self, var, block):
        super().__call__(var, block)
        # dt = exp(u);  softplus^-1(dt) = log(exp(dt) - 1)
        for op, attrs in (("exp", {}), ("exp", {}), ("scale", {"bias": -1.0}),
                          ("log", {})):
            block.append_op(op, inputs={"X": var.name},
                            outputs={"Out": var.name}, attrs=attrs)


class LogRangeInitializer(ConstantInitializer):
    """log(1), log(2), .. along the last axis, the same in every row: a
    state-space layer's ``A_log`` (Mamba's S4D-real initialisation)."""

    def __init__(self):
        super().__init__(1.0)

    def __call__(self, var, block):
        super().__call__(var, block)
        for op, attrs in (("cumsum", {"axis": -1}), ("log", {})):
            block.append_op(op, inputs={"X": var.name},
                            outputs={"Out": var.name}, attrs=attrs)


class NormalInitializer(Initializer):
    def __init__(self, loc=0.0, scale=1.0, seed=0):
        self.loc, self.scale, self.seed = loc, scale, seed

    def __call__(self, var, block):
        block.append_op(
            "gaussian_random",
            outputs={"Out": var.name},
            attrs={
                "shape": list(var.shape),
                "dtype": var.dtype,
                "mean": float(self.loc),
                "std": float(self.scale),
                "seed": self.seed,
            },
        )


class TruncatedNormalInitializer(NormalInitializer):
    def __call__(self, var, block):
        block.append_op(
            "truncated_gaussian_random",
            outputs={"Out": var.name},
            attrs={
                "shape": list(var.shape),
                "dtype": var.dtype,
                "mean": float(self.loc),
                "std": float(self.scale),
                "seed": self.seed,
            },
        )


def _fan_in_out(var):
    shape = var.shape
    if len(shape) == 2:
        fan_in, fan_out = shape[0], shape[1]
    elif len(shape) >= 3:
        receptive = math.prod(shape[2:])
        fan_in = shape[1] * receptive
        fan_out = shape[0] * receptive
    else:
        fan_in = fan_out = shape[0] if shape else 1
    return fan_in, fan_out


class XavierInitializer(Initializer):
    """Glorot init (reference: initializer.py XavierInitializer)."""

    def __init__(self, uniform=True, fan_in=None, fan_out=None, seed=0):
        self.uniform = uniform
        self.fan_in, self.fan_out = fan_in, fan_out
        self.seed = seed

    def __call__(self, var, block):
        fi, fo = _fan_in_out(var)
        fi = self.fan_in if self.fan_in is not None else fi
        fo = self.fan_out if self.fan_out is not None else fo
        if self.uniform:
            limit = math.sqrt(6.0 / (fi + fo))
            UniformInitializer(-limit, limit, self.seed)(var, block)
        else:
            std = math.sqrt(2.0 / (fi + fo))
            NormalInitializer(0.0, std, self.seed)(var, block)


class MSRAInitializer(Initializer):
    """He/Kaiming init (reference: initializer.py MSRAInitializer)."""

    def __init__(self, uniform=True, fan_in=None, seed=0):
        self.uniform = uniform
        self.fan_in = fan_in
        self.seed = seed

    def __call__(self, var, block):
        fi, _ = _fan_in_out(var)
        fi = self.fan_in if self.fan_in is not None else fi
        if self.uniform:
            limit = math.sqrt(6.0 / fi)
            UniformInitializer(-limit, limit, self.seed)(var, block)
        else:
            std = math.sqrt(2.0 / fi)
            NormalInitializer(0.0, std, self.seed)(var, block)


class NumpyArrayInitializer(Initializer):
    def __init__(self, value: np.ndarray):
        self.value = np.asarray(value)

    def __call__(self, var, block):
        # Stage the literal through an assign-from-constant: store it on the
        # op so the kernel can close over it.
        flat = [float(x) for x in self.value.reshape(-1)]
        block.append_op(
            "assign_value",
            outputs={"Out": var.name},
            attrs={
                "shape": list(self.value.shape),
                "dtype": var.dtype,
                "values": flat,
            },
        )


# Aliases matching the reference's public names.
Constant = ConstantInitializer
Uniform = UniformInitializer
LogUniform = LogUniformInitializer
Normal = NormalInitializer
TruncatedNormal = TruncatedNormalInitializer
Xavier = XavierInitializer
MSRA = MSRAInitializer


def _global_weight_initializer():
    return XavierInitializer()


def _global_bias_initializer():
    return ConstantInitializer(0.0)
