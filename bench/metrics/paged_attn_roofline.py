"""Kernel: the least time the chip could spend on the window's paged
attention (bytes and operations that the live rows' KV lengths need, see
``bench.costs``), over the device time of the ``paged_kv_attention``
events in the trace. Computed the same way whatever implements the
kernel."""
from bench import costs


def read(run):
    if not run.trace or run.trace["kernel_s"] <= 0 or not run.peaks:
        return None
    calls = (run.in_window(run.probe.decode_calls)
             + run.in_window(run.probe.prefill_calls))
    least = costs.least_time(run.shape, run.peaks, calls)
    return 100.0 * least / run.trace["kernel_s"]
