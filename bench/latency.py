"""Per-request latencies from the harness's host-clock stamps."""
from __future__ import annotations

from typing import List

from bench import stats


def ttfts(run) -> List[float]:
    """Due time to first token, for every request due in the window. One
    with no first token by the drain's end counts as later than any
    other."""
    recs = run.window_records
    got = [r.first - r.due for r in recs if r.first is not None]
    return got + [stats.latest_for_missing(got, run.t_drain_end - r.due)
                  for r in recs if r.first is None]


def tpots(run) -> List[float]:
    """Per request with at least two tokens delivered in the window: the
    time from its first to its last token there, over its tokens less
    one."""
    out = []
    for r in run.records:
        st = [(t, n) for t, n in r.stamps if run.t0 <= t <= run.t_close]
        k = sum(n for _, n in st)
        if k >= 2:
            out.append((st[-1][0] - st[0][0]) / (k - 1))
    return out
