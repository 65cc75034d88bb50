"""Operations and bytes of the Granite-4.0-H family, from shapes alone
(the conventions of perf/flops.py: a multiply-add counts 2,
recomputation does not count, embedding lookups are left out). Its
layers are of two kinds (``layer_kinds``), each a mixer and a dense
SwiGLU, and are counted by kind. The Mamba-2 scan in its chunked form IS
matmuls (four products a chunk and head) and counts in ``train_flops``,
at the chunk the kernels run (``kernel_chunk``), with ``C B^T`` once a
GROUP: the work the mathematics needs, whatever a kernel's head blocks
make again."""

from __future__ import annotations

from typing import Dict, List

KINDS = {"mamba": "mamba2", "attention": "attn"}


def layer_kinds(cfg: Dict) -> List[str]:
    """The kind of each layer the configuration holds, by its PUBLISHED
    index (``first_layer`` ..)."""
    first = int(cfg.get("first_layer", 0))
    return [KINDS[cfg["layer_types"][i]]
            for i in range(first, first + int(cfg["num_hidden_layers"]))]


def count(cfg: Dict, kind: str) -> int:
    return layer_kinds(cfg).count(kind)


def sizes(cfg: Dict) -> Dict[str, int]:
    heads, p = int(cfg["mamba_n_heads"]), int(cfg["mamba_d_head"])
    g, n = int(cfg["mamba_n_groups"]), int(cfg["mamba_d_state"])
    h = int(cfg["num_attention_heads"])
    return {"d": int(cfg["hidden_size"]), "heads": heads, "p": p,
            "e": heads * p, "g": g, "n": n, "gn": g * n, "h": h,
            "hk": int(cfg["num_key_value_heads"]),
            "dh": int(cfg["hidden_size"]) // h,
            "f": int(cfg["shared_intermediate_size"])}


def mamba2_scan_flops(cfg: Dict, batch: int, t: int, chunk: int) -> float:
    """Forward matmul FLOPs of ONE layer's scan in its chunked form: a
    chunk of C positions and head is C B^T (C x C x n, shared by the
    heads of a group), the decay matrix's product with x dt (C x C x p),
    C times the carried state and B^T x dt into it (C x n x p each)."""
    z = sizes(cfg)
    per = 2.0 * (chunk * chunk * z["n"] * z["g"] / z["heads"]
                 + chunk * chunk * z["p"] + 2 * chunk * z["n"] * z["p"])
    return batch * -(-t // chunk) * z["heads"] * per


def mamba2_scan_cost(cfg: Dict, batch: int, t: int, chunk: int = 128,
                     bytes_per_el: int = 2) -> Dict[str, float]:
    """FLOPs and HBM bytes the Mamba-2 scan calls of one train step
    NEED, forward + backward ONCE (backward = 2 x forward; the chunk the
    backward kernel makes again, a recomputed segment's second forward
    and a head block's own C B^T are not counted). Bytes, counted LOW:
    x, y and their gradients once each at the stream's width, B and C
    and their gradients, and the float32 state saved for each chunk of
    ``chunk`` positions, written once and read once."""
    z = sizes(cfg)
    layers = count(cfg, "mamba2")
    tok = batch * t
    moved = tok * (4 * z["e"] + 4 * z["gn"]) * bytes_per_el
    moved += 2 * batch * -(-t // chunk) * z["heads"] * z["p"] * z["n"] * 4
    return {"flops": layers * 3.0 * mamba2_scan_flops(cfg, batch, t, chunk),
            "bytes": float(layers * moved), "calls": 2 * layers}


def attention_cost(cfg: Dict, batch: int, t: int, bytes_per_el: int = 2
                   ) -> Dict[str, float]:
    """The attention calls of one train step, forward + backward: one
    causal triangle an attention layer, h query heads over hk key/value
    heads of dh (32 / 8 x 64). Forward q.k^T and p.v over the visible
    pairs, backward dv, dp, dq, dk: 12 * pairs * dh a head. Bytes: six
    tensors at the queries' width and six at the keys'."""
    z = sizes(cfg)
    n = count(cfg, "attn")
    pairs = t * (t + 1) // 2
    return {"flops": n * 12.0 * batch * z["h"] * pairs * z["dh"],
            "bytes": float(n * 6 * batch * t * (z["h"] + z["hk"]) * z["dh"]
                           * bytes_per_el),
            "calls": 2 * n}


def layer_params(cfg: Dict, kind: str) -> float:
    """Weights of a layer that a token's row is multiplied by: its
    mixer's projections and the SwiGLU's three matrices."""
    z = sizes(cfg)
    d = z["d"]
    mlp = 3 * d * z["f"]
    if kind == "mamba2":
        return d * (2 * z["e"] + 2 * z["gn"] + z["heads"]) + z["e"] * d + mlp
    return d * (z["h"] + 2 * z["hk"]) * z["dh"] + z["h"] * z["dh"] * d + mlp


def train_flops(cfg: Dict, batch: int, t: int) -> float:
    """Forward + backward matmul FLOPs of one train step: every token
    runs its layer's projections (``layer_params``) and the tied head
    over the held rows of the vocabulary; backward = 2 x forward; plus
    the attention calls and the Mamba-2 scans' chunk products. A
    recomputed segment's second forward is NOT counted: ``step.mfu.train``
    reads the work the step is for."""
    tok = batch * t
    layers = sum(layer_params(cfg, k) for k in layer_kinds(cfg))
    head = int(cfg["hidden_size"]) * int(cfg["vocab_size"])
    return (3.0 * 2 * tok * (layers + head)
            + attention_cost(cfg, batch, t)["flops"]
            + mamba2_scan_cost(cfg, batch, t,
                               int(cfg["kernel_chunk"]))["flops"])
