"""Batched XLA backend: a whole step group in one jitted dispatch.

The group's tiles are stacked into ``(G, steps, m, k)`` / ``(G, steps,
k, n)`` and the whole thing runs as a single jit-compiled call: the
per-item k-chains are folded into one ``(m, steps*k) @ (steps*k, n)``
contraction — a task's entire k-loop becomes ONE long-K GEMM (the
Stream-K-style work-centric unit) — and the G items ride a single
batched matmul.  XLA sees one well-shaped kernel instead of
``G * steps`` interpreted calls plus ``G * (steps-1)`` interpreted
adds, so both the per-step dispatch tax and the tiny-matmul
inefficiency disappear.  ``jax.jit`` keys its compile cache on the
abstract ``(G, steps, m, k, n, dtype)`` signature, so recurring tile
shapes (the common case: every full tile of a matrix shares one
shape) hit warm compiled executables.

Dtype handling (multi-precision contract, see ``repro.core.dtypes``):
tiles are staged in the group's *storage* dtype — float32 groups move
half the bytes of float64, bfloat16/float16 a quarter — and the
contraction accumulates at the engine's best precision: float64 only
when ``jax_enable_x64`` is on (default CPU jax computes in float32);
float32 for every narrower storage dtype (the MXU-canonical f32
accumulation for bf16/f16 inputs).  The result is cast back to the
group's promoted storage dtype, so callers always get the dtype
contract of the numpy engine.  ``jax.jit`` keys its compile cache on
the abstract ``(shape, dtype)`` signature, so every storage precision
gets its own specialized executable.  Float64 workloads on a
32-bit-configured jax trade precision, which is why the parity suite
pins float32 inputs.
"""
from __future__ import annotations

import threading
from typing import Sequence

import numpy as np

from .. import telemetry
from .base import (ExecutionBackend, GroupResult, StepGroupKey, build_once,
                   placed_chip)


@build_once
def _group_contract():
    """Lazily import jax and build the jitted group kernel (one function;
    jit's own cache specializes it per shape/dtype)."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def run(a, b):  # a: (g, s, m, k)   b: (g, s, k, n)
        g, s, m, k = a.shape
        n = b.shape[-1]
        a2 = jnp.transpose(a, (0, 2, 1, 3)).reshape(g, m, s * k)
        b2 = b.reshape(g, s * k, n)
        # f32 accumulation for every sub-f64 storage dtype (f32, bf16,
        # f16); the caller casts back to the storage dtype afterwards.
        # HIGHEST: on a TPU the default precision multiplies f32
        # operands in one bf16 pass, which is not an f32 GEMM
        pref = jnp.float64 if a.dtype == jnp.float64 else jnp.float32
        return jnp.matmul(a2, b2, preferred_element_type=pref,
                          precision=jax.lax.Precision.HIGHEST)

    return run


def engine_dtype(want: str) -> str:
    """The *staging* dtype for a storage dtype: float64 narrows to
    float32 when jax runs without x64 (see module doc); float32 and
    the half precisions stage as-is — low-precision groups keep their
    small byte footprint and widen only inside the MXU/accumulator.
    Deliberately uncached — ``jax_enable_x64`` can be toggled at
    runtime and must be re-read per dispatch."""
    if want == "float64":
        import jax

        if not jax.config.jax_enable_x64:
            return "float32"
    return want


def stack_items(key: StepGroupKey, a_tiles: Sequence[np.ndarray],
                b_tiles: Sequence[np.ndarray]):
    """(G*steps) tile lists -> contiguous (G, steps, m, k) /
    (G, steps, k, n) staging buffers in the engine dtype (one fused
    cast-copy per tile; halves transfer bytes for f64-stored data on a
    32-bit engine)."""
    g = len(a_tiles) // key.steps
    eng = engine_dtype(key.dtype)
    a = np.empty((len(a_tiles), key.m, key.k), dtype=eng)
    b = np.empty((len(b_tiles), key.k, key.n), dtype=eng)
    for i, tile in enumerate(a_tiles):
        a[i] = tile
    for i, tile in enumerate(b_tiles):
        b[i] = tile
    return (a.reshape(g, key.steps, key.m, key.k),
            b.reshape(g, key.steps, key.k, key.n))


# (kernel, item shapes, dtype, chip, items) of every smaller group
# compiled ahead of its first use
_SMALLER: set = set()
_SMALLER_LOCK = threading.Lock()


def _compile_smaller_groups(fn, a, b, chip) -> None:
    """Run ``fn`` once on every group of fewer items of this shape, the
    first time a placed chip sees the shape: a threads-mode call ends
    with smaller groups on whichever chips finish last or steal, and a
    compile there would stall that chip in the middle of a later call."""
    sig = (fn, a.shape[1:], b.shape[1:], str(a.dtype), chip.id)
    with _SMALLER_LOCK:
        todo = [g for g in range(1, a.shape[0]) if (sig, g) not in _SMALLER]
        _SMALLER.update((sig, g) for g in todo)
    for g in todo:
        fn(a[:g], b[:g]).block_until_ready()


def run_staged(fn, key: StepGroupKey, a_tiles: Sequence[np.ndarray],
               b_tiles: Sequence[np.ndarray], engine: str) -> GroupResult:
    """One group on its chip: stage its tiles on the host, hand them to
    the chip the runtime placed this worker on (:func:`placed_chip`) with
    a ``device_put`` waited on (so ``blasx.h2d`` ends where
    ``blasx.kernel`` starts), run the jitted ``fn(a, b)`` there, where
    its operands are, and fetch its products in the group's dtype.  On a
    placed chip, the first group of a shape also compiles its smaller
    groups (:func:`_compile_smaller_groups`)."""
    import jax

    chip = placed_chip()
    with telemetry.span("blasx.stage"):
        a, b = stack_items(key, a_tiles, b_tiles)
    with telemetry.span("blasx.h2d"):
        telemetry.count("h2d_bytes", a.nbytes + b.nbytes)
        a, b = jax.block_until_ready(jax.device_put((a, b), chip))
    if chip is not None:
        _compile_smaller_groups(fn, a, b, chip)
    with telemetry.span("blasx.kernel"):
        out = fn(a, b).block_until_ready()
    with telemetry.span("blasx.d2h"):
        out = np.asarray(out)
        if out.dtype != np.dtype(key.dtype):
            out = out.astype(key.dtype)
    return GroupResult(list(out), launches=1, engine=engine)


class JaxBackend(ExecutionBackend):
    name = "jax"

    def run_group(self, key: StepGroupKey, a_tiles: Sequence[np.ndarray],
                  b_tiles: Sequence[np.ndarray]) -> GroupResult:
        return run_staged(_group_contract(), key, a_tiles, b_tiles,
                          self.name)
