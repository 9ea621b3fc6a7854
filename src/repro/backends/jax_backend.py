"""Batched XLA backend: a whole step group in one jitted dispatch.

The group's tiles go to the chip as they are, one ``device_put`` for
the group, and are stacked there into ``(G, steps, m, k)`` / ``(G,
steps, k, n)``; the whole thing runs as a single jit-compiled call: the
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

import functools
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


def _as_sent(tiles: Sequence[np.ndarray], eng: str):
    """One side of a group as it goes to the chip: each tile as it is
    where it is contiguous in the engine dtype, or as the contiguous
    array it is the ``.T`` view of, the whole side turned where more of
    its tiles are such views (the chip turns them back).  A tile that
    is neither, or of another dtype, is copied or cast on the host.
    Returns the tiles to send, whether they go transposed, and the
    bytes copied."""
    transposed = (sum(t.flags.f_contiguous for t in tiles)
                  > sum(t.flags.c_contiguous for t in tiles))
    sent, copied = [], 0
    for tile in tiles:
        view = tile.T if transposed else tile
        out = np.ascontiguousarray(view, dtype=eng)
        if out is not view:
            copied += out.nbytes
        sent.append(out)
    return tuple(sent), transposed, copied


@build_once
def _stacked(fn):
    """``fn`` on a group's tiles as they arrived on the chip: one jitted
    call that stacks each side's ``G * steps`` tiles (item-major) into
    the ``(G, steps, m, k)`` / ``(G, steps, k, n)`` operands ``fn``
    takes, turning back a side sent as its tiles' transposes, and runs
    ``fn`` inline.  ``steps`` and the two turns are static: one compile
    per group shape, dtype and orientation of its sides."""
    import jax
    import jax.numpy as jnp

    @functools.partial(jax.jit, static_argnums=(2, 3, 4))
    def run(a_tiles, b_tiles, steps, turn_a, turn_b):
        a, b = jnp.stack(a_tiles), jnp.stack(b_tiles)
        if turn_a:
            a = jnp.swapaxes(a, 1, 2)
        if turn_b:
            b = jnp.swapaxes(b, 1, 2)
        g = a.shape[0] // steps
        return fn(a.reshape(g, steps, *a.shape[1:]),
                  b.reshape(g, steps, *b.shape[1:]))

    return run


# (wrapper, tile shapes, dtype, steps, turns, chip, items) of every
# smaller group compiled ahead of its first use
_SMALLER: set = set()
_SMALLER_LOCK = threading.Lock()


def _compile_smaller_groups(run, a, b, steps, turns, chip) -> None:
    """Run ``run`` once on every group of fewer items of this shape, the
    first time a placed chip sees the shape: a threads-mode call ends
    with smaller groups on whichever chips finish last or steal, and a
    compile there would stall that chip in the middle of a later call."""
    sig = (run, a[0].shape, b[0].shape, str(a[0].dtype), steps, turns,
           chip.id)
    with _SMALLER_LOCK:
        todo = [g for g in range(1, len(a) // steps)
                if (sig, g) not in _SMALLER]
        _SMALLER.update((sig, g) for g in todo)
    for g in todo:
        run(a[:g * steps], b[:g * steps], steps, *turns).block_until_ready()


def run_staged(fn, key: StepGroupKey, a_tiles: Sequence[np.ndarray],
               b_tiles: Sequence[np.ndarray], engine: str) -> GroupResult:
    """One group on its chip: hand its tiles as they are (:func:`_as_sent`)
    to the chip the runtime placed this worker on (:func:`placed_chip`)
    with one ``device_put`` waited on (so ``blasx.h2d`` ends where
    ``blasx.kernel`` starts), stack them there and run the jitted
    ``fn(a, b)`` in the same call (:func:`_stacked`), and fetch its
    products in the group's dtype.  On a placed chip, the first group of
    a shape also compiles its smaller groups
    (:func:`_compile_smaller_groups`)."""
    import jax

    chip = placed_chip()
    eng = engine_dtype(key.dtype)
    with telemetry.span("blasx.stage"):
        a, turn_a, copied_a = _as_sent(a_tiles, eng)
        b, turn_b, copied_b = _as_sent(b_tiles, eng)
        telemetry.count("stage_copy_bytes", copied_a + copied_b)
    with telemetry.span("blasx.h2d"):
        telemetry.count("h2d_bytes", sum(t.nbytes for t in a + b))
        a, b = jax.block_until_ready(jax.device_put((a, b), chip))
    run = _stacked(fn)
    if chip is not None:
        _compile_smaller_groups(run, a, b, key.steps, (turn_a, turn_b), chip)
    with telemetry.span("blasx.kernel"):
        out = run(a, b, key.steps, turn_a, turn_b).block_until_ready()
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
