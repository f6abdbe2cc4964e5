"""99th percentile of how late each request due in the window was
submitted after its due time (milliseconds): a starved load generator
shows here, not as a fast server."""
from chipbench.stats import percentile


def read(rec):
    p = percentile((s.submit - s.due for s in rec.due_in_window()), 99)
    return None if p is None else 1e3 * p
