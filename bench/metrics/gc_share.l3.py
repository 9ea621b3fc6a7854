"""runtime layer (Python's garbage collector): share (%) of the window
in the collections that interrupted a span of the library (``blasx.gc``)."""
from bench import program


def read(rec):
    return program.self_share(rec, "blasx.gc")
