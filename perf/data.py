"""Traffic generation: ONE general generator per kind of traffic, driven
by the parameters in a cell's file (perf/workloads/<cell>.json).

Every seed gets the SAME multiset of sizes and arrival gaps, in another
order: the work of a run does not depend on the seed, only its order
does. So runs with different seeds differ by noise, not by load.

Token contents and the feed contract are copied from
paddle_tpu.models.transformer.make_batch / bert.make_batch (PERF.md
lists the originals); only the length draw differs, as said above."""

from __future__ import annotations

import math
from statistics import NormalDist
from typing import Dict, List

import numpy as np


def _rng(seed: int, salt: int = 0) -> np.random.RandomState:
    # --seed may pass 2**31; RandomState takes up to 2**32 - 1
    return np.random.RandomState((int(seed) * 1000003 + salt) % (2 ** 32))


def spaced_lengths(n: int, lo: int, hi: int) -> np.ndarray:
    """n lengths evenly spaced over [lo, hi]: the uniform distribution's
    quantiles, the same multiset for every seed."""
    return np.round(lo + (np.arange(n) + 0.5) * (hi - lo) / n).astype(
        np.int64)


# ---------------------------------------------------------------------------
# training feeds
# ---------------------------------------------------------------------------

def train_feeds(traffic: Dict, seed: int, make_batch, streams: int = 1
                ) -> List[Dict[str, np.ndarray]]:
    """``traffic["feeds"]`` padded batches of ``traffic["batch"]``
    sequences of ``traffic["seq_len"]`` positions. Real lengths are
    spread evenly over ``traffic["real_len"]`` = [lo, hi] across ALL
    the feeds and shuffled by the seed, once per stream (a translation
    pair has two: source and target). ``make_batch(r, seq, *lens)``
    is the family's (perf/families/<family>.py)."""
    n_feeds, batch, seq = traffic["feeds"], traffic["batch"], \
        traffic["seq_len"]
    lo, hi = traffic["real_len"]
    r = _rng(seed)
    lens = [r.permutation(spaced_lengths(n_feeds * batch, lo, hi))
            for _ in range(streams)]
    return [make_batch(r, seq, *(ln[i * batch:(i + 1) * batch]
                                 for ln in lens))
            for i in range(n_feeds)]


def transformer_batch(cfg, r, seq, src_lens, trg_lens):
    b = len(src_lens)
    src = r.randint(3, cfg["src_vocab_size"], (b, seq)).astype(np.int64)
    trg = r.randint(3, cfg["trg_vocab_size"], (b, seq)).astype(np.int64)
    lbl = r.randint(3, cfg["trg_vocab_size"], (b, seq)).astype(np.int64)
    src_pad = (np.arange(seq)[None, :] < src_lens[:, None]).astype(
        np.float32)
    trg_pad = (np.arange(seq)[None, :] < trg_lens[:, None]).astype(
        np.float32)
    return {"src_ids": src * src_pad.astype(np.int64),
            "trg_ids": trg * trg_pad.astype(np.int64),
            "lbl_ids": lbl, "src_pad_mask": src_pad,
            "trg_pad_mask": trg_pad}


def bert_batch(cfg, r, seq, lens):
    b = len(lens)
    ids = r.randint(4, cfg["vocab_size"], (b, seq)).astype(np.int64)
    type_ids = np.zeros((b, seq), np.int64)
    type_ids[:, seq // 2:] = 1
    pad = (np.arange(seq)[None, :] < lens[:, None]).astype(np.float32)
    mlm = np.full((b, seq), -1, np.int64)
    for row in range(b):
        n_mask = max(1, int(lens[row] * 0.15))
        pos = r.choice(int(lens[row]), n_mask, replace=False)
        mlm[row, pos] = ids[row, pos]
        ids[row, pos] = 3  # [MASK]
    ids = ids * pad.astype(np.int64)
    nsp = r.randint(0, 2, (b, 1)).astype(np.int64)
    return {"input_ids": ids, "token_type_ids": type_ids, "pad_mask": pad,
            "mlm_labels": mlm, "nsp_labels": nsp}


# ---------------------------------------------------------------------------
# serving requests (open loop)
# ---------------------------------------------------------------------------

def lognormal_lengths(n: int, median: float, sigma: float, lo: int,
                      hi: int) -> np.ndarray:
    """n lengths at the log-normal's evenly spaced quantiles, clipped:
    the same multiset for every seed."""
    nd = NormalDist()
    q = (np.arange(n) + 0.5) / n
    z = np.asarray([nd.inv_cdf(float(p)) for p in q])
    return np.clip(np.round(median * np.exp(sigma * z)), lo, hi).astype(
        np.int64)


def poisson_gaps(n: int, rate: float) -> np.ndarray:
    """n inter-arrival gaps at the exponential distribution's evenly
    spaced quantiles, rescaled to sum to exactly n / rate seconds."""
    q = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-q)
    return gaps * (n / rate) / gaps.sum()


def serve_requests(cfg: Dict, traffic: Dict, seed: int, seconds: float
                   ) -> List[Dict]:
    """The open-loop schedule of one run: rate x seconds requests, each
    {"due": seconds from the window's start, "src": ids, "max_new": n}.

    Poisson arrivals (``gap_cv`` absent or 1) or gamma inter-arrival
    gaps with coefficient of variation ``gap_cv`` (bursts), source
    lengths log-normal, output cap ``out_ratio`` x source length."""
    n = max(1, int(round(traffic["rate_per_s"] * seconds)))
    r = _rng(seed, 1)
    cv = float(traffic.get("gap_cv", 1.0))
    if cv == 1.0:
        gaps = poisson_gaps(n, traffic["rate_per_s"])
        gaps = r.permutation(gaps)
    else:
        shape = 1.0 / (cv * cv)
        gaps = _rng(0, 2).gamma(shape, 1.0, n)   # fixed multiset
        gaps = r.permutation(gaps * (n / traffic["rate_per_s"])
                             / gaps.sum())
    due = np.cumsum(gaps) - gaps[0]
    sl = traffic["src_len"]
    lens = r.permutation(lognormal_lengths(
        n, sl["median"], sl["sigma"], sl["min"], sl["max"]))
    out = traffic["max_new"]
    reqs = []
    for i in range(n):
        m = int(lens[i])
        cap = int(min(max(math.ceil(out["ratio"] * m), out["min"]),
                      out["max"]))
        reqs.append({
            "due": float(due[i]),
            "src": r.randint(3, cfg["src_vocab_size"], (m,)).astype(
                np.int64),
            "max_new": cap})
    return reqs
