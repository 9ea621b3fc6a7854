"""runtime layer (``core/runtime.py`` phase 1: ALRU / MESI-X acquire and
host materialize of every input tile): share (%) of the window in the
self time of the library's ``blasx.gather`` spans."""
from bench import program


def read(rec):
    return program.self_share(rec, "blasx.gather")
