"""Scheduler: 90th percentile of the time from a request's due time to
its admission (the program's ``_do_admit`` call), over the window's
requests that were admitted."""
from bench import stats


def read(run):
    if run.probe is None:
        return None
    waits = [run.probe.admit_t[r.req.rid] - r.due for r in run.window_records
             if r.req is not None and r.req.rid in run.probe.admit_t]
    return stats.percentile(waits, 90)
