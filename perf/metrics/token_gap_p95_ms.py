"""95th percentile over all gaps between successive tokens of one
request."""

from perf import serve_stats, stats


def read(run):
    return stats.percentile(serve_stats.token_gaps_ms(run), 95.0)
