"""90th percentile over requests of the time per output token after the
first, in the window (host clock), in milliseconds."""
from bench import latency, stats


def read(run):
    v = stats.percentile(latency.tpots(run), 90)
    return None if v is None else 1000.0 * v
