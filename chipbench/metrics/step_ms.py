"""Host time inside ``engine.step()`` over the window, per step
(milliseconds)."""


def read(rec):
    st = rec.steps_in_window()
    return 1e3 * sum(s.t1 - s.t0 for s in st) / len(st) if st else None
