"""Tokens trained inside the window over the window, data fetch and the
per-step loss read included."""


def read(run):
    return run.steps * run.tokens_per_step / run.window_s if run.steps else None
