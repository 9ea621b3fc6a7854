"""backends layer (``backends/jax_backend.py``: ``jax.device_put`` of a
group's staging buffers, waited on): share (%) of the window in the self
time of the library's ``blasx.h2d`` spans.  On a TPU v5e this holds the
whole transfer; the release of the host staging buffers that follows it
lands in the ``blasx.kernel`` wait, which has no metric (PERF.md §7)."""
from bench import program


def read(rec):
    return program.self_share(rec, "blasx.h2d")
