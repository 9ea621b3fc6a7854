"""kernels layer (``kernels/matmul.py`` Pallas, XLA ``dot``): share (%) of
the matmul kernels' device time that their roofline needs, at the
published bf16 peak and HBM bandwidth (``bench/trace.py``).  Silent when
no matmul kernel ran in the traced window."""


def read(rec):
    if rec.trace is None or rec.trace.roofline is None:
        return None
    return rec.trace.roofline["share"]
