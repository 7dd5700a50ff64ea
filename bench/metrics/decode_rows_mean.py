"""Scheduler: live rows per decode step in the window, from the program's
counters (``serve.gen_tokens`` over ``serve.decode_steps``)."""


def read(run):
    steps = run.counter_delta("serve.decode_steps")
    if steps <= 0:
        return None
    return run.counter_delta("serve.gen_tokens") / steps
