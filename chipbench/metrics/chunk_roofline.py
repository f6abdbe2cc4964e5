"""Least time of the prefill-chunk dispatches' needed work
(``work.prefill`` over the cached history) over those programs' device
time in the traced stretch, in percent. A chunk runs inside a decode
step and stalls every live slot, so it sets the long gaps between
tokens."""
from chipbench.stats import roofline_share


def read(rec):
    return roofline_share(rec, ["chunk_paged", "chunk_contiguous"])
