"""Serving's share of the chip's peak: the algorithm's operations for every
token processed in the window (2 per weight, plus attention over the live
positions), over the window times the bf16 peak."""
from harness.readers import window_flops


def read(run):
    flops = window_flops(run)
    if not flops or run.window_s <= 0:
        return None
    return 100.0 * flops / (run.busy_window_s * run.peaks["bf16_flops_per_s"])
