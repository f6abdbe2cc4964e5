"""95th percentile of every gap between consecutive output tokens of
every request, for gaps that end in the window (milliseconds)."""
from chipbench.stats import gaps_in_window, percentile


def read(rec):
    p = percentile(gaps_in_window(rec), 95)
    return None if p is None else 1e3 * p
