"""One file per Level-3 routine, found by the routine's name.

Each holds ``out_shape(shapes, kw)``, ``flops(shapes, kw)`` (the
routine's standard count) and ``reference(args, kw, rows=None,
mm=numpy.matmul)``: the routine in plain numpy, written from its BLAS
definition and independent of the library.  ``rows`` asks for those rows
of the output only; ``mm`` is the product every multiply goes through,
so the same code computes the float64 reference and, given a product in
lower precision, the control (``bench/check.py``).
"""
