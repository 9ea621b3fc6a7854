"""Execution-backend protocol: *how* tile math runs, decoupled from
*where/when* the scheduler runs it.

The BLASX runtime (``repro.core.runtime``) treats tiles as the basic
task unit: the scheduler picks a device and an order; every tile
k-step then has to be multiplied somewhere.  The seed implementation
executed each step as one interpreted host call — faithful scheduling,
but every step paid full per-call dispatch overhead.  An
:class:`ExecutionBackend` instead receives a *group* of same-shape
steps (grouped by the runtime per device batch) and may execute the
whole group as one batched dispatch — the software analogue of packing
concurrent tile kernels onto a stream.

Contract
--------
* Tiles arriving at a backend are already **materialized**: the fill
  mask (triangular/symmetric storage semantics) and the paper-§III-C
  transpose trick were applied on the host, so ``a_tiles[i]`` is
  ``(m, k)`` and ``b_tiles[i]`` is ``(k, n)`` exactly as multiplied.
  The originating ``op/trans/fill`` metadata still rides on the
  :class:`StepGroupKey` so backends can specialize (the Pallas backend
  only routes full-fill square groups to the TPU kernel).
* ``run_group`` must return one accumulator per *item* (a
  ``key.steps``-deep multiply-accumulate chain; see
  :class:`StepGroupKey`), in order, as numpy arrays (the runtime's
  cache/ledger layer is host-centric).
* Backends must be callable from several device worker threads at once
  (``mode="threads"``); compile caches are the only allowed state, and
  each is built once (:func:`build_once`).
* A group runs on the chip the runtime placed its caller on
  (:func:`on_chip`, read back by :func:`placed_chip`): the runtime's
  device ``d`` is bound to one of the host's chips and every group it
  dispatches goes there.  Outside such a placement a group runs on
  JAX's default device.
* ``launches`` in the returned :class:`GroupResult` is the number of
  kernel dispatches the group cost — the ledger currency behind the
  ``launches saved`` statistic.
"""
from __future__ import annotations

import abc
import contextlib
import contextvars
import dataclasses
import functools
import threading
from typing import List, Optional, Sequence

import numpy as np


# index into ``jax.local_devices()`` of the chip the current step group
# goes to; each threads-mode worker runs in a context of its own
_CHIP: contextvars.ContextVar = contextvars.ContextVar("blasx_chip",
                                                       default=None)


@contextlib.contextmanager
def on_chip(index: Optional[int]):
    """Step groups dispatched inside run on ``jax.local_devices()[index]``
    (None: JAX's default device)."""
    token = _CHIP.set(index)
    try:
        yield
    finally:
        _CHIP.reset(token)


def placed_chip():
    """The JAX device the current step group goes to, or None for the
    default device."""
    index = _CHIP.get()
    if index is None:
        return None
    import jax

    return jax.local_devices()[index]


def build_once(factory):
    """``functools.lru_cache`` for kernel factories whose first call per
    key makes one kernel even when several device workers ask at once
    (a second kernel would compile every shape again)."""
    cached = functools.lru_cache(maxsize=None)(factory)
    lock = threading.Lock()

    @functools.wraps(factory)
    def get(*args):
        with lock:
            return cached(*args)

    return get


@dataclasses.dataclass(frozen=True)
class StepGroupKey:
    """Batch signature: items sharing a key are dispatched together.

    One *item* is a ``steps``-deep multiply-accumulate chain
    ``acc = sum_j a_j @ b_j`` — a task's whole k-loop when the task is
    signature-uniform (the Stream-K-style work-centric unit), or a
    single step (``steps == 1``) when the runtime had to split a
    mixed-signature task.  ``m/k/n`` describe the *effective*
    (post-materialization) shape of one step's operands; ``dtype`` is
    the promoted accumulate dtype the caller expects back."""

    op: str        # originating routine ("gemm", "syrk", ...)
    transa: bool
    transb: bool
    fill_a: str    # task.FILL_* constants of the stored tiles
    fill_b: str
    m: int
    k: int
    n: int
    dtype: str
    steps: int = 1  # k-steps contracted per item

    @property
    def flops_per_item(self) -> int:
        return 2 * self.m * self.k * self.n * self.steps

    @property
    def full_fill(self) -> bool:
        """Plain GEMM-shaped multiply chain (the Pallas fast path)."""
        return self.fill_a == "full" and self.fill_b == "full"


@dataclasses.dataclass
class GroupResult:
    """What one grouped dispatch produced."""

    products: List[np.ndarray]   # one accumulator per item, in order
    launches: int                # kernel dispatches this group cost
    engine: str                  # engine that actually ran ("numpy"|"jax"|"pallas")


class ExecutionBackend(abc.ABC):
    """One batched tile-op dispatcher (see module docstring)."""

    name: str = "?"

    @abc.abstractmethod
    def run_group(self, key: StepGroupKey, a_tiles: Sequence[np.ndarray],
                  b_tiles: Sequence[np.ndarray]) -> GroupResult:
        """Execute ``len(a_tiles) // key.steps`` items — each the
        ``key.steps``-deep chain ``sum_j a[i*steps+j] @ b[i*steps+j]``
        over same-shape tiles (item-major order) — as one logical
        dispatch wherever the engine allows; returns one accumulator
        per item."""

    def __repr__(self) -> str:  # pragma: no cover - debugging nicety
        return f"{type(self).__name__}()"
