"""Page pool: the largest share of the pool's usable pages in use (live
rows and cached prefixes), sampled after every scheduler tick in the
window."""


def read(run):
    return 100.0 * max(run.kv_used) if run.kv_used else None
