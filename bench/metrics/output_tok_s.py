"""Tokens delivered to clients in the window, over the window's length."""


def read(run):
    n = sum(k for r in run.records for t, k in r.stamps
            if run.t0 <= t <= run.t_close)
    return n / run.seconds
