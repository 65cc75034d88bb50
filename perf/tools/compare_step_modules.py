"""python perf/tools/compare_step_modules.py <a.txt> <b.txt>

Are two lowered step modules (lower_cell.py --dump) the same program?
The StableHLO around the kernels is compared byte for byte. A Mosaic
kernel's body rides in its custom call as serialised MLIR WITH source
locations: the checkout's path and the line of every kernel statement.
So two checkouts at different paths, or an edit that only moves a
kernel's lines, change the bytes of a module that is the same program.
Each body is therefore parsed and printed without debug information,
and those texts are compared. Prints one sha256 a side over both
parts: equal hashes, same program."""

import base64
import difflib
import hashlib
import re
import sys


def normalised(path):
    from jax._src.lib import tpu
    from jax._src.lib.mlir import ir

    bodies = []

    def body(match):
        ctx = ir.Context()
        ctx.allow_unregistered_dialects = True
        tpu.register_dialect(ctx)
        with ctx:
            module = ir.Module.parse(base64.b64decode(match.group(1)))
            bodies.append(module.operation.get_asm(enable_debug_info=False))
        return f'"body": "<kernel {len(bodies) - 1}>"'

    with open(path) as f:
        outer = re.sub(r'\\22body\\22: \\22([A-Za-z0-9+/=]+)\\22', body,
                       f.read())
    return outer, bodies


def main():
    (a, a_bodies), (b, b_bodies) = (normalised(p) for p in sys.argv[1:3])
    print(f"kernels {len(a_bodies)} / {len(b_bodies)}; around them "
          f"{'equal' if a == b else 'DIFFERENT'}; kernel bodies "
          f"{'equal' if a_bodies == b_bodies else 'DIFFERENT'}")
    for i, (x, y) in enumerate(zip(a_bodies, b_bodies)):
        if x != y:
            print(f"kernel {i}:")
            print("\n".join(list(difflib.unified_diff(
                x.splitlines(), y.splitlines(), lineterm="", n=0))[:20]))
    if a != b:
        print("\n".join(list(difflib.unified_diff(
            a.splitlines(), b.splitlines(), lineterm="", n=0))[:20])[:4000])
    for text, bodies in ((a, a_bodies), (b, b_bodies)):
        print("sha256 without source locations",
              hashlib.sha256((text + "".join(bodies)).encode()).hexdigest())
    return 0 if (a, a_bodies) == (b, b_bodies) else 1


if __name__ == "__main__":
    sys.exit(main())
