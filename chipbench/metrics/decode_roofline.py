"""Least time of the decode dispatches' needed work (``work.decode``:
weights once, live KV read, new KV written) over the decode programs'
device time in the traced stretch, in percent."""
from chipbench.stats import roofline_share


def read(rec):
    return roofline_share(rec, ["decode"])
