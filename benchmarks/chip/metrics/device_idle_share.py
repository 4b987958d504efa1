"""device_idle_share: the traced window's share in which no operation
ran on the chip (1 - the union of its op intervals over the window),
averaged over the cell's chips."""


def read(rec):
    s = rec.summary
    return None if s is None else 100.0 * s.idle_share
