"""Output tokens produced inside the window, over the window."""
from harness.readers import tokens_in_window


def read(run):
    return tokens_in_window(run) / run.window_s if run.window_s > 0 else None
