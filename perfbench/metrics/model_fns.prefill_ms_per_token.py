"""Host time in ``prefill_fn`` (output synced) per prompt token it covers:
the first block's prefill and the replay of every uncovered token."""
from harness.readers import ms_per


def read(run):
    return ms_per(run, ("prefill", "replay"))
