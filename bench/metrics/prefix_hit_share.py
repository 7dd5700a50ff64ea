"""Page pool: prompt tokens served from the prefix cache, as a share of
the prompt tokens admitted in the window (``serve.prefix_hit_tokens`` over
``serve.prefill_tokens``). Nothing to read where the cache is off."""


def read(run):
    if run.cell.conf["server"].get("prefix_cache") != "on":
        return None
    admitted = run.counter_delta("serve.prefill_tokens")
    if admitted <= 0:
        return None
    return 100.0 * run.counter_delta("serve.prefix_hit_tokens") / admitted
