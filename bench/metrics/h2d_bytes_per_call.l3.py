"""backends layer: bytes the library handed to ``jax.device_put`` (its
``h2d_bytes`` counter) per API call in the window; an exact count."""
from bench import program


def read(rec):
    return program.per_call(rec, "h2d_bytes")
