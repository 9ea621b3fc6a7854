"""backends layer (``backends/jax_backend.py`` ``stack_items``: the host copy
of a group's tiles into its staging buffers): share (%) of the window in
the self time of the library's ``blasx.stage`` spans."""
from bench import program


def read(rec):
    return program.self_share(rec, "blasx.stage")
