"""Hand-written pure-JAX SE-ResNeXt-50 train step (same shapes/dtypes as
bench_family.py's se_resnext config: b=128, 224x224, bf16 AMP compute,
fp32 params, momentum) to isolate the achievable step time on this chip
from the Program-IR lowering — the framework-overhead-is-zero leg of the
SE-ResNeXt prove-or-kill (VERDICT r4 item 1a), mirroring what
benchmarks/purejax_ref.py settled for ResNet-50. Diagnostic only.

Run: python benchmarks/purejax_seresnext.py
"""

import time

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

B = 128
STAGES = [3, 4, 6, 3]
FILTERS = [128, 256, 512, 1024]
CARD = 32
RED = 16


def conv(x, w, stride=1, groups=1):
    k = w.shape[0]
    p = (k - 1) // 2
    return lax.conv_general_dilated(
        x, w, (stride, stride), [(p, p), (p, p)],
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        feature_group_count=groups)


def bn(x, p, name):
    """One-pass E[x],E[x^2] batch-stat BN in affine y=k*x+c form — the
    same formulation ops/nn_ops.py batch_norm emits."""
    xf = x.astype(jnp.float32)
    m = jnp.mean(xf, axis=(0, 1, 2))
    m2 = jnp.mean(jnp.square(xf), axis=(0, 1, 2))
    var = m2 - jnp.square(m)
    inv = lax.rsqrt(var + 1e-5) * p[name + ".s"]
    return (x * inv.astype(x.dtype) +
            (p[name + ".b"] - m * inv).astype(x.dtype))


def conv_bn(x, p, name, stride=1, groups=1, relu=True):
    y = bn(conv(x, p[name + ".w"].astype(jnp.bfloat16), stride, groups),
           p, name)
    return jax.nn.relu(y) if relu else y


def se(x, p, name):
    c = x.shape[-1]
    pool = jnp.mean(x.astype(jnp.float32), axis=(1, 2))
    s = jax.nn.relu(pool @ p[name + ".w1"] + p[name + ".b1"])
    e = jax.nn.sigmoid(s @ p[name + ".w2"] + p[name + ".b2"])
    return x * e[:, None, None, :].astype(x.dtype)


def block(x, p, name, filters, stride):
    y = conv_bn(x, p, name + ".c0")
    y = conv_bn(y, p, name + ".c1", stride=stride, groups=CARD)
    y = conv_bn(y, p, name + ".c2", relu=False)
    y = se(y, p, name + ".se")
    if x.shape[-1] == 2 * filters and stride == 1:
        short = x
    else:
        short = conv_bn(x, p, name + ".sc", stride=stride, relu=False)
    return jax.nn.relu(short + y)


def forward(p, img):
    x = conv_bn(img, p, "stem", stride=2)
    x = lax.reduce_window(x, -jnp.inf, lax.max, (1, 3, 3, 1),
                          (1, 2, 2, 1), [(0, 0), (1, 1), (1, 1), (0, 0)])
    for si, (n, f) in enumerate(zip(STAGES, FILTERS)):
        for bi in range(n):
            x = block(x, p, f"b{si}_{bi}", f,
                      2 if bi == 0 and si != 0 else 1)
    x = jnp.mean(x.astype(jnp.float32), axis=(1, 2))
    return x @ p["fc.w"] + p["fc.b"]


def init_params(rng):
    p = {}

    def cw(name, k, ci, co):
        p[name + ".w"] = jnp.asarray(
            rng.randn(k, k, ci, co) * np.sqrt(2.0 / (k * k * ci)),
            jnp.float32)
        p[name + ".s"] = jnp.ones((co,), jnp.float32)
        p[name + ".b"] = jnp.zeros((co,), jnp.float32)

    cw("stem", 7, 3, 64)
    cin = 64
    for si, (n, f) in enumerate(zip(STAGES, FILTERS)):
        for bi in range(n):
            name = f"b{si}_{bi}"
            cw(name + ".c0", 1, cin, f)
            cw(name + ".c1", 3, f // CARD, f)
            cw(name + ".c2", 1, f, 2 * f)
            c2 = 2 * f
            p[name + ".se.w1"] = jnp.asarray(
                rng.randn(c2, c2 // RED) * np.sqrt(2.0 / c2), jnp.float32)
            p[name + ".se.b1"] = jnp.zeros((c2 // RED,), jnp.float32)
            p[name + ".se.w2"] = jnp.asarray(
                rng.randn(c2 // RED, c2) * np.sqrt(2.0 / (c2 // RED)),
                jnp.float32)
            p[name + ".se.b2"] = jnp.zeros((c2,), jnp.float32)
            if cin != c2 or (bi == 0 and si != 0):
                cw(name + ".sc", 1, cin, c2)
            cin = c2
    p["fc.w"] = jnp.asarray(rng.randn(cin, 1000) * 0.01, jnp.float32)
    p["fc.b"] = jnp.zeros((1000,), jnp.float32)
    return p


def loss_fn(p, img, label):
    logits = forward(p, img)
    lse = jax.nn.logsumexp(logits, axis=-1)
    nll = lse - jnp.take_along_axis(logits, label[:, None], axis=1)[:, 0]
    return jnp.mean(nll)


@jax.jit
def step(p, mom, img, label):
    loss, g = jax.value_and_grad(loss_fn)(p, img, label)
    new_m = {k: 0.9 * mom[k] + g[k] for k in g}
    new_p = {k: p[k] - 0.1 * new_m[k] for k in p}
    return new_p, new_m, loss


def main():
    rng = np.random.RandomState(0)
    p = init_params(rng)
    mom = {k: jnp.zeros_like(v) for k, v in p.items()}
    img = jnp.asarray(rng.randn(B, 224, 224, 3) * 0.5, jnp.bfloat16)
    label = jnp.asarray(rng.randint(0, 1000, (B,)), jnp.int32)

    t0 = time.perf_counter()
    p, mom, loss = step(p, mom, img, label)
    jax.block_until_ready(loss)
    print(f"compile+first: {time.perf_counter() - t0:.1f}s loss={float(loss):.3f}")

    for w in range(3):
        t0 = time.perf_counter()
        for _ in range(30):
            p, mom, loss = step(p, mom, img, label)
        jax.block_until_ready(loss)
        dt = (time.perf_counter() - t0) / 30
        fwd_flops = 8.47e9  # analytic fwd GFLOP/image
        mfu = 3 * fwd_flops * B / dt / 197e12
        print(f"window {w}: {dt*1e3:.1f} ms/step  "
              f"{B/dt:.0f} img/s  MFU {mfu:.3f}")


if __name__ == "__main__":
    main()
