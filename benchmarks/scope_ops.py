"""python benchmarks/scope_ops.py <cell>.xplane.pb[.gz] <steps> <op substring>

The device ops of one traced run whose scope's op type holds the
substring (``scaled_dot_product_attention`` finds the op and its grad
op), ms a step by instruction, the layers summed: what an op costs
BESIDE its kernels. The trace is what a run leaves under
``PERF_KEEP_TRACE=<dir>``, the steps its log's "traced N steps". PR 49
read the backward op's 5.7 ms of float32 copies and a reduction with it
(PERF.md section 5); perf/tools/scope_table.py has the table by scope."""

import collections
import gzip
import os
import re
import shutil
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    from perf import spans, trace

    path, steps, want = sys.argv[1], int(sys.argv[2]), sys.argv[3]
    if path.endswith(".gz"):
        with tempfile.NamedTemporaryFile(suffix=".xplane.pb",
                                         delete=False) as tmp, \
                gzip.open(path, "rb") as src:
            shutil.copyfileobj(src, tmp)
        path = tmp.name
    table = collections.Counter()
    chips = spans._chips(spans.load(path))
    for _, ops, _ in chips:
        tf_ops = {e[0]: e[3] for e in ops}
        for name, self_ns in trace.self_times([e[:3] for e in ops]):
            sc = spans.parse_scope(tf_ops[name]) if tf_ops[name] else None
            if sc and want in sc["op"]:
                label = re.sub(r"[.]\d+", "", trace.label(name))
                table[sc["op"], label] += self_ns / steps / len(chips) / 1e6
    for (op, label), ms in sorted(table.items(), key=lambda kv: -kv[1]):
        print(f"{ms:9.3f}  {op}  {label}")


if __name__ == "__main__":
    main()
