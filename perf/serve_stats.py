"""Latencies of a serve window, from the loop's own stamps. Every one
counts from the instant the request was DUE."""

from typing import List


def _recs(run):
    return run.window.get("requests", [])


def ttft_ms(run) -> List[float]:
    """First token handed out minus due time, per request due in the
    window. A request that failed, was refused, or has no token counts
    as the window's length."""
    out = []
    for r in _recs(run):
        if r.get("ok") and r["stamps"]:
            out.append((r["stamps"][0] - r["due"]) * 1e3)
        elif r.get("ok") and r["done_at"] is not None:
            # ended by the end token before any other: the (empty)
            # answer was handed out when the request ended
            out.append((r["done_at"] - r["due"]) * 1e3)
        else:
            out.append(run.window["seconds"] * 1e3)
    return out


def token_gaps_ms(run) -> List[float]:
    """Every gap between successive tokens of one request."""
    out = []
    for r in _recs(run):
        s = r["stamps"]
        out.extend((b - a) * 1e3 for a, b in zip(s, s[1:]))
    return out


def late_ms(run) -> List[float]:
    """How late the generator submitted against its own schedule."""
    return [(r["submitted"] - r["due"]) * 1e3 for r in _recs(run)
            if r["submitted"] is not None]


def queue_wait_ms(run) -> List[float]:
    """Due time to admission into a slot."""
    return [(r["admit"] - r["due"]) * 1e3 for r in _recs(run)
            if r["admit"] is not None]
