"""Model FLOP utilization of the whole traced stretch: the operations
the model needed for every token it processed (``work.py``) over the
stretch's seconds times the chips' bf16 peak, in percent. It bounds
every dispatch kind's roofline, so it stays a reading where a later
program takes a kind off the path."""
from chipbench.stats import mfu


def read(rec):
    return mfu(rec)
