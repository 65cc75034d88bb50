"""What XLA plans AROUND a change, read off the compiled step, no chip.

    python benchmarks/step_plan_diff.py dump <train cell> <out.hlo> [--root <checkout>]
    python benchmarks/step_plan_diff.py diff <parent.hlo> <change.hlo>

``dump`` compiles a cell's train step for a described v5e exactly as
``perf/tools/lower_cell.py`` of ``--root`` does (this checkout by
default; a parent unpacked under .parent/ otherwise) and keeps the
compiled module's text. ``diff`` lists the fusions that only one side
has, by op_name, result shape and XLA's own ``estimated_cycles``
(1.5 GHz on a v5e), and their sums: a kernel that wins alone can lose
in the step through what XLA does around it (which operand gets VMEM,
what is rematerialised: PERF.md section 6, PR 46, where the sum read
+1.8M cycles, 1.2 ms, for smallthinker-train-s16384 and its trace +1.6
ms). An estimate is not a time; it says where to look before a chip
run does.
"""

import collections
import os
import re
import runpy
import sys


def dump(cell, out, root):
    import jax

    compile_ = jax.stages.Lowered.compile

    def keep(self, *a, **kw):
        compiled = compile_(self, *a, **kw)
        with open(out, "w") as f:
            f.write(compiled.as_text())
        return compiled

    jax.stages.Lowered.compile = keep
    sys.argv = ["lower_cell.py", cell]
    runpy.run_path(os.path.join(root, "perf", "tools", "lower_cell.py"),
                   run_name="__main__")


def plan(path):
    """Counter of (op_name, result shape, estimated cycles) over the
    module's fusions, a rematerialised one under its first name."""
    rows = collections.Counter()
    for line in open(path):
        m = re.search(r"^\s*(?:ROOT )?%[\w.\-]+ = (.*?) fusion\(", line)
        if not m:
            continue
        op = re.search(r'op_name="([^"]*)"', line)
        cycles = re.search(r'"estimated_cycles":"(\d+)"', line)
        rows[(op.group(1) if op else "",
              re.sub(r"\{[^}]*\}", "", m.group(1))[:90],
              int(cycles.group(1)) if cycles else 0)] += 1
    return rows


def diff(a, b):
    a, b = plan(a), plan(b)
    for side, only in (("parent", a - b), ("change", b - a)):
        total = sum(k[2] * n for k, n in only.items())
        print(f"only the {side} has {sum(only.values())} fusions, "
              f"{total / 1e6:.2f}M estimated cycles "
              f"({total / 1.5e6:.2f} ms at 1.5 GHz):")
        for (op, shape, cycles), n in sorted(only.items(),
                                             key=lambda kv: -kv[0][2])[:16]:
            print(f"  {cycles:>9} x{n} {op} {shape}")


if __name__ == "__main__":
    if sys.argv[1] == "dump":
        root = (sys.argv[sys.argv.index("--root") + 1]
                if "--root" in sys.argv else
                os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
        dump(sys.argv[2], os.path.abspath(sys.argv[3]), os.path.abspath(root))
    else:
        diff(sys.argv[2], sys.argv[3])
