"""95th percentile of first token minus DUE time, over every request
due in the window (a failed one counts as the window's length)."""

from perf import serve_stats, stats


def read(run):
    return stats.percentile(serve_stats.ttft_ms(run), 95.0)
