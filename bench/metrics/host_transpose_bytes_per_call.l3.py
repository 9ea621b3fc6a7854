"""runtime layer: bytes the library copied into whole-operand host
transposes (its ``host_transpose_bytes`` counter: the side-R routines
that still reduce to the left side) per API call in the window; an
exact count."""
from bench import program


def read(rec):
    return program.per_call(rec, "host_transpose_bytes")
