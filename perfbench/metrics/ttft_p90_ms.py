"""Time to first token, p90 over every request due in the window, from its
due time; a request with no first token by the window's end counts there."""
from harness.readers import percentile, ttft_s


def read(run):
    v = percentile(ttft_s(run), 90)
    return None if v is None else 1000.0 * v
