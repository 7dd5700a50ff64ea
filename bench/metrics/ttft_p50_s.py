"""Median time from a request's due time to its first token, as the
client sees it (host clock), over every request due in the window."""
from bench import latency, stats


def read(run):
    return stats.percentile(latency.ttfts(run), 50)
