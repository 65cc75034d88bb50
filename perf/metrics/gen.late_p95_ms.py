"""How late the load generator submitted against its own schedule: a
starved generator must not read as a fast server."""

from perf import serve_stats, stats


def read(run):
    return stats.percentile(serve_stats.late_ms(run), 95.0)
