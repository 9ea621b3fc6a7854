"""runtime layer (``core/runtime.py`` phase 3: alpha/beta, the TRSM solve,
triangle masks, write-back on the host): share (%) of the window in the
self time of the library's ``blasx.finalize`` spans."""
from bench import program


def read(rec):
    return program.self_share(rec, "blasx.finalize")
