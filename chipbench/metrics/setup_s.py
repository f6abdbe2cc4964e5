"""Seconds from process start until the first request may be sent:
weights, engine build, compilation or cache loads, and warm-up."""


def read(rec):
    return rec.setup_s
