"""backends layer (``backends/jax_backend.py``: ``np.asarray`` of a group's
ready products, and their cast): share (%) of the window in the self
time of the library's ``blasx.d2h`` spans."""
from bench import program


def read(rec):
    return program.self_share(rec, "blasx.d2h")
