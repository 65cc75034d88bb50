"""Time an expert matrix's weight gradient and Adam step on the chip,
the two passes against the one call, at the cells' calls:

    chiprun -- python benchmarks/tgmm_adam_candidates.py [--calls olmoe_gate ...]

P ``tgmm`` (the gradient to HBM as bf16) and ``AdamStep.after`` behind
it, one jitted function whose state is donated: what a step ran before
the update moved into the kernel. F ``tgmm_adam`` at ``adam_tile``'s
tile (none where it refuses the call: a width off the lanes, a share
whose rows outweigh its matrices); F@tk,tn the same at tiles of the
matrix given by hand. Each form is held
to P's result first (the moments to the gradient's bf16 rounding), then
timed: the median wall time of 10 calls (host clock around
``block_until_ready``), after two warm ones. Group sizes are an uneven
router's (a Dirichlet draw over the experts), and for a held share sum
to the live rows. The table goes to
chiprun_out/tgmm_adam_candidates.json.
"""

import argparse
import json
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
OUT = os.path.join(ROOT, "chiprun_out", "tgmm_adam_candidates.json")
# call: (m rows, k, n, experts, live rows or None): lhs [m, k]^T g [m, n]
CALLS = {
    "olmoe_gate": (65536, 2048, 1024, 64, None),
    "olmoe_down": (65536, 1024, 2048, 64, None),
    "qwen3next_gate": (81920, 2048, 512, 32, 5120),
    "qwen3next_down": (81920, 512, 2048, 32, 5120),
    "sdar_gate": (65536, 2048, 768, 16, 8192),
    "joyai_gate": (32768, 2048, 768, 16, 2048),
    "lfm2moe_gate": (32768, 2048, 1536, 8, 4096),
    "nemotron_up": (24576, 2688, 1856, 8, 1536),
    "nemotron_down": (24576, 1856, 2688, 8, 1536),
    "laguna_gate": (65536, 2048, 512, 16, 4096),
    "lfm2moe_down": (32768, 1536, 2048, 8, 4096),
    "smallthinker_gate": (98304, 2560, 768, 8, 12288),
    "smallthinker_down": (98304, 768, 2560, 8, 12288),
    # the rehearsal's, through the interpreter where there is no chip
    "tiny": (512, 256, 256, 4, None),
}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--calls", nargs="*", default=sorted(set(CALLS) - {"tiny"}))
    ap.add_argument("--tiles", nargs="*", default=[],
                    help="tk,tn pairs to try beside adam_tile's")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.parallel import grouped_matmul as gm

    if jax.default_backend() != "tpu":
        assert args.calls == ["tiny"], "the cells' calls need the chip"
        gm._INTERPRET = True
    bf = jnp.bfloat16
    table = {}
    for call in args.calls:
        m, k, n, e, live = CALLS[call]
        r = np.random.RandomState(len(call))
        share = r.dirichlet(np.full(e, 4.0))
        sizes = np.floor(share * (live or m)).astype(np.int32)
        sizes[0] += (live or m) - sizes.sum()
        key = jax.random.PRNGKey(0)
        ks = jax.random.split(key, 5)
        lhs = jax.random.normal(ks[0], (m, k), bf)
        g = jax.random.normal(ks[1], (m, n), bf) * 0.01
        gs = jnp.asarray(sizes)
        tile = gm.gmm_tile(m, k, n, e, bf, live_rows=live)
        fused = gm.adam_tile(tile, k, n, e, live or m)

        def fresh():
            return (jax.random.normal(ks[2], (e, k, n), jnp.float32) * 0.1,
                    jax.random.normal(ks[3], (e, k, n), jnp.float32) * 1e-3,
                    jnp.abs(jax.random.normal(ks[4], (e, k, n),
                                              jnp.float32)) * 1e-4)

        def step_of(state):
            return gm.AdamStep(state, jnp.float32(1e-3), None, 0.9, 0.999,
                               1e-8)

        def two_passes(lhs, g, gs, state):
            return step_of(state).after(gm.tgmm(lhs, g, gs, tile))

        def one_call(at):
            return lambda lhs, g, gs, state: gm.tgmm_adam(
                lhs, g, gs, at, step_of(state))

        forms = {"P": two_passes}
        if fused:
            forms["F"] = one_call(fused)
        for pair in args.tiles:
            tk, tn = (int(x) for x in pair.split(","))
            if k % tk == 0 and n % tn == 0 and (tile[0], tk, tn) != fused:
                forms[f"F@{tk},{tn}"] = one_call((tile[0], tk, tn))
        row = {"tile": tile, "adam_tile": fused, "sizes_max": int(sizes.max())}
        want = None
        for name, fn in forms.items():
            jitted = jax.jit(fn, donate_argnums=(3,))
            try:
                got = jitted(lhs, g, gs, fresh())
                got = [np.asarray(x[:2]) for x in got]
            except Exception as ex:     # a tile Mosaic refuses
                row[name] = f"{type(ex).__name__}: {str(ex)[:200]}"
                continue
            if want is None:
                want = got
            else:
                # moments: linear in the gradient, which P rounded to bf16
                for a, b in zip(got[1:], want[1:]):
                    np.testing.assert_allclose(
                        a, b, rtol=2e-2, atol=1e-2 * np.abs(b).max())
            state = fresh()
            times = []
            for i in range(12):
                t0 = time.perf_counter()
                state = jitted(lhs, g, gs, state)
                jax.block_until_ready(state)
                times.append(time.perf_counter() - t0)
            row[name] = round(statistics.median(times[2:]) * 1e3, 4)
        table[call] = row
        print(call, json.dumps(row), flush=True)
    os.makedirs(os.path.dirname(OUT), exist_ok=True)
    with open(OUT, "w") as f:
        json.dump({"device": str(jax.devices()[0].device_kind),
                   "ms_a_call": table}, f, indent=1)


if __name__ == "__main__":
    main()
