"""Least time of the traced decode dispatches' attention (the family's
``decode_attention_work`` of the ``keys`` and ``rows`` on their spans)
over the device time of ``attention``-scoped operations in decode
programs, in percent (``spans.decode_attention_roofline``)."""
from chipbench import spans


def read(rec):
    if rec.spans is None:
        return None
    return spans.decode_attention_roofline(rec.spans, rec.needed, rec.peaks)
