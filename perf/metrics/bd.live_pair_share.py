"""Over the block-masked attention calls lowered in this process: the
score pairs the mask lets through (L^2 + B L a head) over the pairs the
kernels' walk computes for them
(``paddle_tpu.parallel.flash_attention.bhtd_pairs`` for each call's
tile, pass and block length, from the rows of
pt_attention_dispatch_total that carry ``mask`` = ``block_diffusion``
and ``band`` = ``skip``; it counts only with telemetry on, that is in
traced runs), in %. A walk computes whole blocks, so it reads under 100:
the room a finer walk of the edge blocks has. None where the program has
no such label or function, or lowered no such call in kernels."""

import re

from perf import bd_spans


def read(run):
    try:
        from paddle_tpu.parallel.flash_attention import bhtd_pairs
    except ImportError:
        return None
    computed = live = 0
    for labels, calls in bd_spans.masked_rows():
        shape = re.match(r"b(\d+) tq(\d+) tk(\d+) h(\d+)", labels["shape"])
        tile = re.match(r"hb(\d+) bq(\d+) bk(\d+)", labels.get("tile", ""))
        if labels.get("band") != "skip" or not (shape and tile):
            continue
        b, tq, tk, h = (int(x) for x in shape.groups())
        got = bhtd_pairs(
            tq, tk, tuple(int(x) for x in tile.groups()), False,
            form=labels.get("form"), block_diffusion=int(labels["block"]))
        computed += calls * b * h * got[0]
        live += calls * b * h * got[1]
    return 100.0 * live / computed if computed else None
