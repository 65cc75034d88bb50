"""Over the attention calls under a selection lowered in this process:
the score pairs the selection lets through (min(p + 1, topk) a query,
perf/flops_keye.selected_pairs) over the pairs the kernels' walk
computes for them
(``paddle_tpu.parallel.flash_attention.bhtd_pairs`` for each call's tile
and pass, from the rows of pt_attention_dispatch_total that carry
``sel`` = ``operand``; it counts only with telemetry on, that is in
traced runs), in %. The first form walks the causal triangle and masks,
so it reads about 23 at 2048 of 16,384: the room a walk that gathers the
chosen keys has. None where the program has no such label or function,
or lowered no such call in kernels."""

import re

from perf import dsa_spans, flops_keye


def read(run):
    try:
        from paddle_tpu.parallel.flash_attention import bhtd_pairs
    except ImportError:
        return None
    topk = (run.config.get("sa_config") or {}).get("topk")
    computed = live = 0
    for labels, calls in dsa_spans.selected_rows():
        shape = re.match(r"b(\d+) tq(\d+) tk(\d+) h(\d+)", labels["shape"])
        tile = re.match(r"hb(\d+) bq(\d+) bk(\d+)", labels.get("tile", ""))
        if labels.get("sel") != "operand" or not (shape and tile and topk):
            continue
        b, tq, tk, h = (int(x) for x in shape.groups())
        got = bhtd_pairs(tq, tk, tuple(int(x) for x in tile.groups()), True,
                         form=labels.get("form"))
        computed += calls * b * h * got[0]
        live += calls * b * h * flops_keye.selected_pairs(tq, int(topk))
    return 100.0 * live / computed if computed else None
