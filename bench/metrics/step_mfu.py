"""Whole step: model operations of the tokens processed in the traced
window (prefill and decode: every layer's matmuls, attention from the KV
lengths, the LM head for decoded tokens; ``bench.costs``) over the window
times the chips times their peak bf16 FLOP/s."""
from bench import costs


def read(run):
    if not run.trace or not run.peaks or run.trace["window_s"] <= 0:
        return None
    flops = sum(costs.model_flops(run.shape, rows, head=True)
                for rows in run.in_window(run.probe.decode_calls))
    flops += sum(costs.model_flops(run.shape, rows, head=False)
                 for rows in run.in_window(run.probe.prefill_calls))
    peak = run.trace["window_s"] * run.chips * run.peaks["bf16_flops"]
    return 100.0 * flops / peak
