"""Output tokens stamped inside the window over the window's seconds."""


def read(rec):
    n = sum(1 for s in rec.sent for t in s.stamps if rec.w0 <= t < rec.w1)
    return n / (rec.w1 - rec.w0)
