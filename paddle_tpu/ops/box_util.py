"""Shared box geometry helpers for the detection op family.

One IoU implementation for every pairwise-xyxy consumer (iou_similarity,
ssd_loss, rpn/proposal ops, detection_map) so the epsilon/clamp
conventions can't drift apart. Convention: zero-clamped edge lengths, no
+1 pixel offsets (the reference mixes both across files; ops needing the
+1 legacy convention, e.g. NMS in vision_ops, keep it locally and say
so)."""

from __future__ import annotations

import jax.numpy as jnp


def xyxy_area(b):
    return jnp.maximum(b[..., 2] - b[..., 0], 0.0) * jnp.maximum(
        b[..., 3] - b[..., 1], 0.0)


def iou_xyxy(a, b):
    """Pairwise IoU: a [..., M, 4], b [..., G, 4] -> [..., M, G]."""
    lt = jnp.maximum(a[..., :, None, :2], b[..., None, :, :2])
    rb = jnp.minimum(a[..., :, None, 2:], b[..., None, :, 2:])
    wh = jnp.maximum(rb - lt, 0.0)
    inter = wh[..., 0] * wh[..., 1]
    union = xyxy_area(a)[..., :, None] + xyxy_area(b)[..., None, :] - inter
    return jnp.where(union > 0, inter / jnp.maximum(union, 1e-10), 0.0)


def greedy_bipartite_match(dist):
    """Greedy bipartite matching core shared by the standalone
    bipartite_match op and the fused ssd_loss (reference:
    detection/bipartite_match_op.cc BipartiteMatch): repeatedly take the
    global argmax of ``dist`` [m, n], record col->row, erase that row
    and column. Returns col_match [n] int32 (-1 unmatched).

    The loop is inherently sequential; a device While at realistic
    scale (m=50 gt, n=8732 priors, b=32) measured ~80 ms/step of
    per-iteration overhead (round-4 SSD-300 trace), so small static
    trip counts unroll into straight-line code XLA fuses.
    """
    import jax

    m, n = dist.shape

    def body(_, state):
        col_match, d = state
        idx = jnp.argmax(d)
        r, c = idx // n, idx % n
        ok = d[r, c] > 0
        col_match = jnp.where(ok, col_match.at[c].set(r), col_match)
        d = jnp.where(ok, d.at[r, :].set(-1.0).at[:, c].set(-1.0), d)
        return col_match, d

    col0 = jnp.full((n,), -1, jnp.int32)
    state = (col0, dist.astype(jnp.float32))
    trip = min(m, n)
    if trip <= 64:
        for i in range(trip):
            state = body(i, state)
        return state[0]
    col_match, _ = jax.lax.fori_loop(0, trip, body, state)
    return col_match
