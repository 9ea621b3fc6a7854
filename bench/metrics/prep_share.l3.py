"""runtime layer (``api/context.py``: tiling raw host arrays, new output
matrices, side-R transposes, dropping ephemeral tiles): share (%) of the
window in the self time of the library's ``blasx.prep`` spans."""
from bench import program


def read(rec):
    return program.self_share(rec, "blasx.prep")
