"""Share of the traced window in which no op ran on the chip, averaged over
the cell's chips (1 - union of op intervals / window)."""


def read(m):
    return 100.0 * m.trace.idle_share
