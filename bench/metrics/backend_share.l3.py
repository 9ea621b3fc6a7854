"""backends layer (``backends/*.run_group``: staging, H2D, launch, D2H):
share (%) of the window spent inside ``run_group``.  Host clock, from the
benchmark's spans."""


def read(rec):
    if rec.spans is None or rec.spans.groups == 0:
        return None
    return 100.0 * rec.spans.group_s / rec.window_s
