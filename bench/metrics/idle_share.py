"""Device (TPU): the share of the traced window in which no operation ran
on the chip, in percent."""


def read(r):
    w = r.trace.window_s
    return 100.0 * (1.0 - r.trace.busy_s / w) if w > 0 else None
