"""Kernel: device time of the ``paged_kv_attention`` events over the
device's busy time in the traced window."""


def read(run):
    if not run.trace or run.trace["busy_s"] <= 0:
        return None
    return 100.0 * run.trace["kernel_s"] / run.trace["busy_s"]
