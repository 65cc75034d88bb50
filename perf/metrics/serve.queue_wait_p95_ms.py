"""Due time to admission into a slot (the handle's admit_ts on the
benchmark's clock)."""

from perf import serve_stats, stats


def read(run):
    return stats.percentile(serve_stats.queue_wait_ms(run), 95.0)
