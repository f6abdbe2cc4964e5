"""Least time of writing the traced dispatches' new keys and values once
(the family's ``kv_write_work`` of the ``new_tokens`` on their spans)
over the device time of ``kv_write``-scoped operations in every
program, in percent (``spans.kv_write_roofline``)."""
from chipbench import spans


def read(rec):
    if rec.spans is None:
        return None
    return spans.kv_write_roofline(rec.spans, rec.needed, rec.peaks)
