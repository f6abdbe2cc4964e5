"""Share of the traced stretch in which no operation ran on the device,
in percent: 100 * (1 - busy / stretch)."""


def read(rec):
    if rec.trace is None:
        return None
    t = rec.trace
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
