"""Time the forms an EDGE block of the BHTD attention kernels can take,
alone on the chip, at the eight decoder cells' calls (bf16, causal; the
windowed calls with their band).

    chiprun -- python benchmarks/attn_bwd_candidates.py --parent .parent \
        [--calls laguna_w512 ...] [--forms whole sub256 ...]

A block that the diagonal or a band's far edge crosses holds dead
sub-tiles (all in the future, or all forgotten). The forms:

- ``whole``: the parent checkout's module (``--parent``: a ``git
  archive`` of the parent commit): every edge block computed whole and
  masked, and the forward under plain ``causal`` masking every live
  block;
- ``nomask``: this tree with no sub-tiles (``_edge_tile`` answering
  None): edge blocks whole, the forward's interior blocks unmasked: what
  the interior masks alone cost (the backward is the parent's);
- ``sub256`` / ``sub128`` / ``sub<sq>x<sk>``: the ONE backward call
  walks an edge block in sub-tiles of that shape, each dead, plain or
  masked by the block's own predicates (``_when_live``); the forward
  works on an edge block whole, so it is ``nomask``'s and is not timed
  again ("=");
- ``grid256``: no sub-tiles, the whole grid at blocks of 256 (four
  times the steps);
- ``program``: the tree as it stands (``_EDGE_SUB`` untouched).

Each form's (out, lse) and (dq, dk, dv) are held to ``whole``'s first;
then ms a call forward and backward, the median of five stretches of 10
calls dispatched back to back (host clock around one
``block_until_ready``), and the score pairs a head's steps compute
against those the mask lets through (``bhtd_pairs``). A call timed alone
reads up to 1.7x its ms inside a cell's step: rank forms by this table,
price them by the cell. The table goes to
chiprun_out/attn_bwd_candidates.json and .md. Needs a TPU. (How the fused
backward's walk was chosen, PR 39: PERF.md section 6.)
"""

import argparse
import importlib.util
import json
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
OUT = os.path.join(ROOT, "chiprun_out", "attn_bwd_candidates")
# call: (b, heads, key/value heads, t, dh, dv, window)
CALLS = {
    "laguna_w512": (1, 64, 8, 8192, 128, 128, 512),
    "laguna_global": (1, 48, 8, 8192, 128, 128, None),
    "smallthinker_w4096": (1, 28, 4, 16384, 128, 128, 4096),
    "smallthinker_global": (1, 28, 4, 16384, 128, 128, None),
    "joyai": (1, 32, 32, 4096, 192, 128, None),
    "qwen3next": (1, 16, 2, 8192, 256, 256, None),
    "olmoe": (2, 16, 16, 4096, 128, 128, None),
    "phi4flash_w512": (1, 20, 10, 4096, 64, 128, 512),
    "phi4flash_global": (1, 20, 10, 4096, 64, 128, None),
    "nemotron3nano": (1, 32, 2, 4096, 128, 128, None),
    "lfm2moe": (1, 32, 8, 8192, 64, 64, None),
}
FORMS = ("whole", "nomask", "sub256", "sub128", "grid256")


def load_parent(path):
    """The parent checkout's flash_attention module, beside this tree's."""
    spec = importlib.util.spec_from_file_location(
        "parent_flash_attention",
        os.path.join(path, "paddle_tpu", "parallel", "flash_attention.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def form_setup(form, fa, parent):
    """-> (module, its _edge_tile while the form is traced, blocks)."""
    if form == "whole":
        return parent, None, None
    if form == "program":
        return fa, fa._edge_tile, None
    if form == "nomask":
        return fa, lambda bq, bk: None, None
    if form == "grid256":
        return fa, lambda bq, bk: None, 256
    sides = [int(x) for x in form[3:].split("x")]
    sq, sk = sides if len(sides) == 2 else sides * 2

    def edge(bq, bk):
        fits = bq % sq == 0 and bk % sk == 0 and (sq, sk) != (bq, bk)
        return (sq, sk) if fits else None
    return fa, edge, None


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--calls", nargs="*", default=list(CALLS))
    ap.add_argument("--forms", nargs="*", default=list(FORMS))
    ap.add_argument("--parent", default=os.path.join(ROOT, ".parent"))
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    if jax.default_backend() != "tpu":
        print("attn_bwd_candidates: no TPU", file=sys.stderr)
        return 2
    from paddle_tpu.parallel import flash_attention as fa
    parent = load_parent(args.parent)

    def ms(f, *a):
        jax.block_until_ready(f(*a))
        took = []
        for _ in range(5):
            t0 = time.perf_counter()
            outs = [f(*a) for _ in range(10)]
            jax.block_until_ready(outs)
            del outs
            took.append((time.perf_counter() - t0) * 1e3 / 10)
        return round(statistics.median(took), 4)

    def worst(a, b):
        a, b = (np.asarray(x, np.float32) for x in (a, b))
        return float(np.abs(a - b).max() / max(np.abs(b).max(), 1e-6))

    table = []
    for name in args.calls:
        b, h, hk, t, dh, dv, window = CALLS[name]
        r = np.random.RandomState(7)

        def rand(*shape, s=1.0):
            return jnp.asarray(r.randn(*shape) * s, jnp.bfloat16)

        q, k = rand(b, h, t, dh, s=0.5), rand(b, hk, t, dh, s=0.5)
        v, g = rand(b, hk, t, dv), rand(b, h, t, dv)
        row = {"call": name, "shape": [b, h, hk, t, dh, dv, window]}
        want = None
        for form in args.forms:
            mod, edge, block = form_setup(form, fa, parent)
            kw = dict(causal=True, window=window, q_block=block,
                      k_block=block)
            held = getattr(mod, "_edge_tile", None)
            if edge is not None:
                mod._edge_tile = edge
            try:
                tile = mod.bhtd_tile(h, t, t, block, block, dh=dh,
                                     group=h // hk, dv=dv)
                cell = {"tile": mod.tile_label(tile)}
                if hasattr(mod, "bhtd_pairs"):
                    computed, live = mod.bhtd_pairs(t, t, tile, True, window)
                    cell.update(edge=mod.edge_label(mod.bhtd_edge_tile(
                        tile, True)), pairs_computed=computed,
                        pairs_live=live)
                fwd = jax.jit(lambda q, k, v: mod.flash_attention_fwd(
                    q, k, v, **kw)).lower(q, k, v).compile()
                out, lse = fwd(q, k, v)
                bwd = jax.jit(
                    lambda q, k, v, out, lse, g: mod.flash_attention_bwd(
                        q, k, v, None, None, out, lse, g, **kw)
                ).lower(q, k, v, out, lse, g).compile()
                got = (out, lse, *bwd(q, k, v, out, lse, g))
                if want is None:
                    want = got
                cell["worst_vs_first"] = [round(worst(a, w), 5)
                                          for a, w in zip(got, want)]
                # (sub-tiles are the backward's: the forward is nomask's)
                cell["fwd_ms"] = "=" if form.startswith(
                    ("sub", "program")) else ms(fwd, q, k, v)
                cell["bwd_ms"] = ms(bwd, q, k, v, out, lse, g)
            except Exception as e:  # a form Mosaic refuses is a row
                cell = {"error": str(e)[:400]}
            finally:
                if edge is not None:
                    mod._edge_tile = held
            row[form] = cell
            print(name, form, cell, flush=True)
        table.append(row)

    os.makedirs(os.path.dirname(OUT), exist_ok=True)
    with open(OUT + ".json", "w") as f:
        json.dump(table, f, indent=1)
    with open(OUT + ".md", "w") as f:
        f.write("| call | " + " | ".join(
            f"{x} fwd / bwd ms" for x in args.forms) + " |\n")
        f.write("| --- |" + " --- |" * len(args.forms) + "\n")
        for row in table:
            cells = [
                "%s / %s" % (row[x].get("fwd_ms", "-"),
                             row[x].get("bwd_ms", "-")) for x in args.forms]
            f.write(f"| {row['call']} | " + " | ".join(cells) + " |\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
