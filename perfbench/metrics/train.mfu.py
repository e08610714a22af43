"""Training's share of the chip's peak: the model's operations per step from
its shapes (forward and backward, recompute not counted) times steps per
second, over the bf16 peak."""
from harness import counts as C


def read(run):
    if not run.steps or run.window_s <= 0:
        return None
    tr = run.traffic
    flops = C.train_flops(run.config, int(tr["batch"]), int(tr["seq"]))
    return 100.0 * flops * run.steps / run.busy_window_s / run.peaks["bf16_flops_per_s"]
