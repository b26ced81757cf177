"""Share of the traced window in which no operation ran on the device."""


def read(r):
    w = r.summary.window_s
    if w <= 0 or not r.summary.busy:
        return None
    return 100.0 * (1.0 - r.summary.busy_s / w)
