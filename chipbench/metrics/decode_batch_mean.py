"""Mean number of slots decoded per decode step over the window's steps
(a step decodes when it returns tokens from the decode dispatch)."""


def read(rec):
    n = [s.decoded for s in rec.steps_in_window() if s.decoded]
    return sum(n) / len(n) if n else None
