"""Device: the share of the traced window in which no operation ran on the
chip, in percent."""


def read(v):
    return None if v.reduced is None else 100.0 * v.reduced.idle_share
