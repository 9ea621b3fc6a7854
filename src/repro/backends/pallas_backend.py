"""Pallas-kernel backend: full-fill tile groups on the repo's TPU kernel.

GEMM-shaped multiply chains over full (non-edge, non-triangular) tiles
are exactly what the repo's Pallas kernel (``repro.kernels.matmul``)
was built for: each item's k-chain folds into one
``(m, steps*k) @ (steps*k, n)`` matmul — long-K, MXU-aligned blocks
chosen by ``kernels.ops`` — and the group runs as one vmapped
``pallas_call`` dispatch.  A shape-keyed cache holds the jitted
batched kernels so each (steps, tile, dtype) signature compiles once
per process — every storage precision (f64/f32/bf16/f16) gets its own
compiled kernel, and the kernel's VMEM accumulator is float32
regardless of storage dtype (``preferred_element_type`` in
``kernels.matmul``), which is the f32-accumulation contract for
low-precision inputs.

Everything else (triangular/symmetric fills, mixed-signature tasks
split into single steps by the runtime) falls back to the batched
:class:`~repro.backends.jax_backend.JaxBackend` path for that group —
still one dispatch per group, just not through the Pallas kernel.

On hosts without a TPU the kernel runs in interpret mode (correct but
slow) — the point there is compositional testing, not speed; see the
README's "Execution backends" section.
"""
from __future__ import annotations

import functools
from typing import Optional, Sequence

import numpy as np

from .base import ExecutionBackend, GroupResult, StepGroupKey
from .jax_backend import JaxBackend, run_staged

# ops whose full-fill steps are plain C += A @ B tile multiplies
_PALLAS_OPS = ("gemm", "syrk", "syr2k", "symm")


@functools.lru_cache(maxsize=None)
def _use_interpret() -> bool:
    import jax

    return jax.default_backend() != "tpu"


@functools.lru_cache(maxsize=None)
def _batched_pallas_contract(steps: int, m: int, k: int, n: int,
                             dtype: str, interpret: bool):
    """Shape-keyed compile cache: one jitted vmapped Pallas matmul per
    (steps, tile shape, dtype) signature."""
    import jax
    import jax.numpy as jnp

    from ..kernels import ops as kops

    del steps, m, k, n, dtype  # cache key only; jit re-specializes

    @jax.jit
    def run(a, b):  # a: (g, s, m, k)   b: (g, s, k, n)
        g, s, mm, kk = a.shape
        nn = b.shape[-1]
        a2 = jnp.transpose(a, (0, 2, 1, 3)).reshape(g, mm, s * kk)
        b2 = b.reshape(g, s * kk, nn)
        return jax.vmap(
            lambda x, y: kops.matmul(x, y, interpret=interpret))(a2, b2)

    return run


class PallasBackend(ExecutionBackend):
    name = "pallas"

    def __init__(self, interpret: Optional[bool] = None):
        self._fallback = JaxBackend()
        self._interpret = interpret

    def _route_to_pallas(self, key: StepGroupKey) -> bool:
        return key.full_fill and key.op in _PALLAS_OPS

    def run_group(self, key: StepGroupKey, a_tiles: Sequence[np.ndarray],
                  b_tiles: Sequence[np.ndarray]) -> GroupResult:
        if not self._route_to_pallas(key):
            return self._fallback.run_group(key, a_tiles, b_tiles)
        interpret = (self._interpret if self._interpret is not None
                     else _use_interpret())
        fn = _batched_pallas_contract(key.steps, key.m, key.k, key.n,
                                      key.dtype, interpret)
        return run_staged(fn, key, a_tiles, b_tiles, self.name)
