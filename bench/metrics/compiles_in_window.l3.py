"""backends layer: executables JAX compiled or loaded inside the window
(``/jax/core/compile/backend_compile_duration`` events); 0 when set-up
warmed every shape."""


def read(rec):
    return float(rec.compiles)
