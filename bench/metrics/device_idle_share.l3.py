"""device layer: share (%) of the traced window in which no operation ran
on the chip (``bench/trace.py``)."""


def read(rec):
    if rec.trace is None:
        return None
    return rec.trace.idle_share
