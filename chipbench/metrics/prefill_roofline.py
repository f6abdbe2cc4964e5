"""Least time of the whole-prompt prefill dispatches' needed work
(``work.prefill``) over those programs' device time in the traced
stretch, in percent. The blocking scheduler runs a prefill between
decode steps and every live slot waits for it, so it sets the long gaps
between tokens."""
from chipbench.stats import roofline_share


def read(rec):
    return roofline_share(rec, ["prefill"])
