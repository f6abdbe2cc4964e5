"""Arithmetic shared by the metric readers."""
from __future__ import annotations

import numpy as np


def percentile(values, q: float) -> float | None:
    """The ``q``-th percentile (numpy's linear interpolation), or None
    for no values."""
    values = list(values)
    return float(np.percentile(values, q)) if values else None


def gaps_in_window(rec):
    """Every gap between consecutive output tokens of every request,
    for gaps that end in the window (seconds)."""
    out = []
    for s in rec.sent:
        st = s.stamps
        out.extend(b - a for a, b in zip(st, st[1:])
                   if rec.w0 <= b < rec.w1)
    return out


def roofline_share(rec, kinds) -> float | None:
    """Least time of the needed work of ``kinds`` dispatches in the
    traced stretch over their device time, in percent."""
    if rec.trace is None or rec.stretch_work is None:
        return None
    work = [rec.stretch_work[k] for k in kinds if k in rec.stretch_work]
    dev = sum(rec.trace["kinds"].get(k, 0.0) for k in kinds)
    if not work or dev <= 0:
        return None
    p = rec.peaks
    least = sum(w.least_seconds(p["bf16_flops"], p["hbm_bytes_s"])
                for w in work)
    return 100.0 * least / dev


def mfu(rec) -> float | None:
    """Operations the model needed in the traced stretch over the
    stretch's length times the chips' bf16 peak, in percent."""
    if rec.trace is None or not rec.stretch_work:
        return None
    flops = sum(w.flops for w in rec.stretch_work.values())
    return 100.0 * flops / (rec.trace["window_s"] * rec.chips
                            * rec.peaks["bf16_flops"])
