"""Operations and bytes of the Phi-4-mini-flash family, from shapes
alone (the conventions of perf/flops.py: a multiply-add counts 2,
recomputation does not count, embedding lookups are left out). Its
layers are of six kinds (``layer_kinds``) and are counted by kind; the
selective scan has no matmul and is counted in BYTES only
(``ssm_scan_cost``): its work is VPU and EUP work that no matmul peak
speaks of, and ``train_flops`` leaves it out as it leaves out every
elementwise op."""

from __future__ import annotations

from typing import Dict, List

from perf.flops_smallthinker import visible_pairs

SCAN_KINDS = ("mamba", "mamba_mem")
ATTENTION_KINDS = ("swa", "full", "cross")


def layer_kinds(cfg: Dict) -> List[str]:
    """The kind of each layer the configuration holds, by its PUBLISHED
    index (``first_layer`` ..): Mamba or window attention in the first
    half of ``model_layers``, then the memory source, the key/value
    source, and GMU or cross-attention."""
    first = int(cfg.get("first_layer", 0))
    count = int(cfg["num_hidden_layers"])
    half = int(cfg.get("model_layers", first + count)) // 2
    out = []
    for i in range(first, first + count):
        ssm_shaped = i % int(cfg["mb_per_layer"]) == 0
        if i < half:
            out.append("mamba" if ssm_shaped else "swa")
        elif i == half:
            out.append("mamba_mem")
        elif i == half + 1:
            out.append("full")
        else:
            out.append("gmu" if ssm_shaped else "cross")
    return out


def sizes(cfg: Dict) -> Dict[str, int]:
    """The widths: the configuration's keys, and HF ``Phi4FlashConfig``'s
    defaults for the Mamba sizes it does not carry."""
    d = int(cfg["hidden_size"])
    return {"d": d, "e": int(cfg.get("mamba_expand", 2)) * d,
            "n": int(cfg.get("mamba_d_state", 16)),
            "taps": int(cfg.get("mamba_d_conv", 4)),
            "r": int(cfg.get("mamba_dt_rank") or -(-d // 16)),
            "f": int(cfg["intermediate_size"]),
            "h": int(cfg["num_attention_heads"]),
            "hk": int(cfg["num_key_value_heads"]),
            "dh": d // int(cfg["num_attention_heads"])}


def mixer_params(cfg: Dict, kind: str) -> int:
    """Weights of a layer's mixer that a token's row is multiplied by."""
    z = sizes(cfg)
    d, e, n, r = z["d"], z["e"], z["n"], z["r"]
    if kind in SCAN_KINDS:
        return d * 2 * e + e * (r + 2 * n) + r * e + e * d
    if kind == "gmu":
        return d * e + e * d
    q, kv = z["h"] * z["dh"], 2 * z["hk"] * z["dh"]
    return d * (q + (0 if kind == "cross" else kv)) + q * d


def attention_cost(cfg: Dict, batch: int, t: int, bytes_per_el: int = 2):
    """The attention calls of one train step, forward + backward: two
    softmax maps a layer, each h / 2 heads of dh over values of 2 dh;
    q.k^T (dh) and p.v (2 dh) forward, dv, dp (2 dh) and dq, dk (dh)
    backward: 6 * pairs * 3 dh a head and map. Pairs: a band in a
    window layer, a triangle in the full and in each cross layer.
    Bytes: q, k, v, o and their gradients, each tensor moved once a
    pass that needs it (q, k, v, o twice, the gradients once)."""
    z = sizes(cfg)
    h, hk, dh = z["h"] // 2, z["hk"] // 2, z["dh"]
    kinds = [k for k in layer_kinds(cfg) if k in ATTENTION_KINDS]
    flops = sum(
        2 * 6 * 2.0 * batch * h * 3 * dh / 2 * visible_pairs(
            t, int(cfg["sliding_window"]) if k == "swa" else None)
        for k in kinds)
    a_map = 3 * batch * t * (h * dh + hk * dh + hk * 2 * dh + h * 2 * dh)
    return {"flops": flops,
            "bytes": float(len(kinds) * 2 * a_map * bytes_per_el),
            "calls": 4 * len(kinds)}


def ssm_scan_cost(cfg: Dict, batch: int, t: int, block: int = 128,
                  bytes_per_el: int = 2) -> Dict[str, float]:
    """HBM bytes the selective-scan calls of one train step need,
    counted LOW: x (the convolution's output), dt, y and their
    gradients once each at the stream's width, z and dz where the call
    is gated (the memory source's is not), B and C and their gradients,
    and the state saved every ``block`` positions (float32, written
    once). The backward pass's second reading of x, dt and z, and of
    the states, is not counted. No FLOPs: the scan has no matmul; its
    state updates (``updates``: t x e x n a call, forward) are VPU and
    EUP work."""
    z = sizes(cfg)
    e, n = z["e"], z["n"]
    kinds = [k for k in layer_kinds(cfg) if k in SCAN_KINDS]
    tok = batch * t
    moved = 0.0
    for k in kinds:
        streams = 6 + (2 if k == "mamba" else 0)
        moved += tok * (streams * e + 4 * n) * bytes_per_el
        moved += batch * -(-t // block) * e * n * 4
    return {"flops": 0.0, "bytes": moved, "calls": 2 * len(kinds),
            "updates": float(len(kinds) * tok * e * n)}


def phi4flash_train_flops(cfg: Dict, batch: int, t: int) -> float:
    """Forward + backward matmul FLOPs of one train step: every token
    runs its layer's mixer projections and MLP and the (tied) head over
    the held rows of the table; backward = 2 x forward; plus the
    attention calls. The selective scan's state updates are not matmul
    FLOPs and are left out (``ssm_scan_cost``)."""
    z = sizes(cfg)
    tok = batch * t
    mlp = 3 * z["d"] * z["f"]
    layers = sum(mixer_params(cfg, k) + mlp for k in layer_kinds(cfg))
    head = z["d"] * int(cfg["vocab_size"])
    return (3.0 * 2 * tok * (layers + head)
            + attention_cost(cfg, batch, t)["flops"])
