"""Gap between consecutive output tokens, p95 over all gaps of all
requests inside the window."""
from harness.readers import itl_s, percentile


def read(run):
    v = percentile(itl_s(run), 95)
    return None if v is None else 1000.0 * v
