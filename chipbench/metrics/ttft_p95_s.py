"""95th percentile, over every request due in the window, of the time
from its due time to its first output token (seconds). A request with
no first token by the end of the drain counts as failed and is left
out here (the result line reports it under ``failed``)."""
from chipbench.stats import percentile


def read(rec):
    return percentile((s.stamps[0] - s.due for s in rec.due_in_window()
                       if s.stamps), 95)
