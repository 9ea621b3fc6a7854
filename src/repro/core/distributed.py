"""Distributed tiled GEMM on the TPU mesh — BLASX's insights, SPMD-native.

The paper's two key communication ideas map onto the ICI ring:

* **L2 tile cache / P2P**: in the ring schedules below, after the first
  step every operand panel a device consumes arrives from its ICI
  *neighbor* (collective_permute), never from a distant shard or the
  host — the paper's "reduce CPU-GPU communication to GPU-GPU
  communication", taken to its limit (0 host traffic in steady state).

* **4-stream overlap**: each ring step's ``ppermute`` of the *next*
  panel is data-independent of the current panel's matmul, so XLA's
  async collectives run the ICI transfer under the MXU compute —
  double-buffered communication/computation overlap by construction.

* **Locality-first scheduling (Eq. 3)**: every device starts with the
  panel it already holds (its "L1-resident" tile) before touching
  remote panels — the +2-for-L1-hit priority, statically scheduled.

Provided collective matmuls (all shard_map kernels):

  ``ring_allgather_matmul``     Y[m, n/d]   = allgather_m(X[m/d, k]) @ W[k, n/d]
  ``ring_reduce_scatter_matmul``Y[m/d, n]   = reduce_m(X[m/d... k/d] @ W[k/d, n])
  ``distributed_gemm``          the out-of-core pod GEMM used by the
                                BLAS-at-pod-scale benchmarks/dry-run.

Each has a ``*_gspmd`` reference twin (plain einsum + jax collectives)
used as oracle and as the paper-faithful "unoptimized" baseline.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def _acc_type(a_dtype, b_dtype):
    """MXU accumulation dtype: at least f32 (the bf16/f32 paths keep
    their historical f32 accumulate bit-for-bit), widened to f64 when
    either operand is 64-bit (jax_enable_x64 serving)."""
    return jnp.promote_types(jnp.float32,
                             jnp.promote_types(a_dtype, b_dtype))


# --------------------------------------------------------------------------
# shard_map bodies (take axis_name; composable inside larger programs)
# --------------------------------------------------------------------------
def ring_allgather_matmul(x_local: jax.Array, w_local: jax.Array,
                          axis_name: str) -> jax.Array:
    """Y_local[m, n/d] = (all-gather of X over ``axis_name``) @ W_local.

    X arrives sequence/row-sharded (m/d rows per device); W is
    column-sharded.  Instead of a monolithic all-gather (cuBLAS-XT's
    "move everything on demand"), panels circulate the ring and each
    device matmuls the panel it currently holds — panel k+1 is in
    flight (ppermute) while panel k multiplies.
    """
    d = lax.axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    m_local, _ = x_local.shape
    n_local = w_local.shape[1]
    perm = [(i, (i + 1) % d) for i in range(d)]

    y = jnp.zeros((d * m_local, n_local),
                  dtype=jnp.promote_types(x_local.dtype, w_local.dtype))
    chunk = x_local
    for s in range(d):
        nxt = lax.ppermute(chunk, axis_name, perm) if s < d - 1 else None
        # the panel now in hand originated at device (idx - s) mod d
        slot = (idx - s) % d
        part = jnp.dot(chunk, w_local, preferred_element_type=_acc_type(
            chunk.dtype, w_local.dtype)).astype(y.dtype)
        # both indices pinned to one dtype: under jax_enable_x64 a bare
        # 0 would be int64 next to the int32 traced slot index
        start = (slot * m_local).astype(jnp.int32)
        y = lax.dynamic_update_slice(y, part, (start, jnp.int32(0)))
        chunk = nxt
    return y


def ring_reduce_scatter_matmul(x_local: jax.Array, w_local: jax.Array,
                               axis_name: str) -> jax.Array:
    """Y_local[m/d, n] = reduce-scatter_m(X_local[m, k/d] @ W_local[k/d, n]).

    Row-parallel layer: every device holds a K-shard; the (m, n)
    partial products are reduce-scattered over rows by a ring in which
    the accumulator hop (ppermute) overlaps the *next* row-block's
    matmul.  The matmul is deliberately blocked by row so only one
    block is computed per ring step (BLASX's k-step interleaving).

    Ragged row counts (``m % d != 0`` — real serving shapes) are padded
    with zero rows up to the next ring multiple, so the returned shard
    is ``ceil(m/d)`` rows and the global output has ``d*ceil(m/d)``
    rows whose tail is zeros; callers slice (``tp_matmul`` /
    ``distributed_gemm`` do).
    """
    d = lax.axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    m = x_local.shape[0]
    mb = -(-m // d)
    if mb * d != m:  # pad-and-slice ragged shards (zero rows are inert)
        x_local = jnp.pad(x_local, ((0, mb * d - m), (0, 0)))
    perm = [(i, (i + 1) % d) for i in range(d)]

    def block(b):
        xs = lax.dynamic_slice_in_dim(x_local, b * mb, mb, axis=0)
        return jnp.dot(xs, w_local, preferred_element_type=_acc_type(
            xs.dtype, w_local.dtype))

    # start with the block that must travel the full ring (locality-first:
    # it is computed from the panel already resident on this device)
    acc = block((idx - 1) % d)
    for s in range(1, d):
        moved = lax.ppermute(acc, axis_name, perm)
        acc = moved + block((idx - 1 - s) % d)   # overlap: matmul vs hop
    return acc.astype(jnp.promote_types(x_local.dtype, w_local.dtype))


# ------------------------------------------------------- gspmd baselines
def gspmd_allgather_matmul(x_local, w_local, axis_name):
    x_full = lax.all_gather(x_local, axis_name, axis=0, tiled=True)
    return jnp.dot(x_full, w_local, preferred_element_type=_acc_type(
        x_full.dtype, w_local.dtype)
                   ).astype(jnp.promote_types(x_local.dtype, w_local.dtype))


def gspmd_reduce_scatter_matmul(x_local, w_local, axis_name):
    d = lax.axis_size(axis_name)
    m = x_local.shape[0]
    mb = -(-m // d)
    if mb * d != m:  # same pad-and-slice contract as the ring twin
        x_local = jnp.pad(x_local, ((0, mb * d - m), (0, 0)))
    part = jnp.dot(x_local, w_local, preferred_element_type=_acc_type(
        x_local.dtype, w_local.dtype))
    out = lax.psum_scatter(part, axis_name, scatter_dimension=0, tiled=True)
    return out.astype(jnp.promote_types(x_local.dtype, w_local.dtype))


MODES = {
    "ring": (ring_allgather_matmul, ring_reduce_scatter_matmul),
    "gspmd": (gspmd_allgather_matmul, gspmd_reduce_scatter_matmul),
}


# --------------------------------------------------------------------------
# High-level: out-of-core pod GEMM (the BLAS library at pod scale)
# --------------------------------------------------------------------------
def distributed_gemm(A: jax.Array, B: jax.Array, mesh: Mesh, *,
                     row_axis: str = "data", col_axis: str = "model",
                     mode: str = "ring") -> jax.Array:
    """C = A @ B on a 2-D device mesh.

    Layout (the tile-algebra layout of §III at shard granularity):
      A : P(row_axis, col_axis)   — both dims sharded (out-of-core tiles)
      B : P(col_axis, None)       — K-sharded
      C : P(row_axis, None)       — row-sharded result

    Every (row_axis) group runs an independent K-reduction over
    col_axis; with ``mode='ring'`` that reduction is the overlap-
    friendly ring reduce-scatter GEMM above, re-gathered to keep C's
    K-replicated layout.

    Ragged shapes (M not divisible by the row axis, K not divisible by
    the column axis, or a row-shard not divisible by the ring size) are
    padded with zeros internally and the result sliced back to
    ``(M, N)`` — the zero padding lives in the tail shard, so the slice
    is exact.
    """
    if mode not in MODES:
        raise ValueError(f"mode must be one of {sorted(MODES)}")
    dr = mesh.shape[row_axis]
    dc = mesh.shape[col_axis]
    m, k = A.shape
    m_pad = -(-m // dr) * dr
    k_pad = -(-k // dc) * dc
    if m_pad != m or k_pad != k:
        A = jnp.pad(A, ((0, m_pad - m), (0, k_pad - k)))
    if k_pad != k:
        B = jnp.pad(B, ((0, k_pad - k), (0, 0)))

    def body(a_blk, b_blk):
        # a_blk: (m/dr, k/dc); b_blk: (k/dc, n)
        if mode == "ring":
            y = ring_reduce_scatter_matmul(a_blk, b_blk, col_axis)
            y = lax.all_gather(y, col_axis, axis=0, tiled=True)
            # the ring kernel pads ragged row-shards up to the next
            # ring multiple; drop those rows so out_specs stay exact
            y = y[:a_blk.shape[0]]
        else:
            part = jnp.dot(a_blk, b_blk, preferred_element_type=_acc_type(
                a_blk.dtype, b_blk.dtype))
            y = lax.psum(part, col_axis).astype(
                jnp.promote_types(a_blk.dtype, b_blk.dtype))
        return y

    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(row_axis, col_axis), P(col_axis, None)),
        out_specs=P(row_axis, None),
        check_vma=False,
    )
    C = fn(A, B)
    if m_pad == m:
        return C
    # M rows cannot split evenly over the row axis, so the sliced
    # result is replicated; explicit-axis meshes need that spelled out
    return C.at[:m].get(out_sharding=NamedSharding(mesh, P(None, None)))


def tp_matmul(x: jax.Array, w: jax.Array, mesh: Mesh, *, axis: str = "model",
              kind: str = "column", mode: str = "ring",
              batch_axis: Optional[str] = "data") -> jax.Array:
    """Tensor-parallel projection for the model zoo.

    kind='column': x is sequence-sharded on ``axis``; W col-sharded;
                   returns activations col-sharded (full sequence).
    kind='row'   : x is feature-sharded on ``axis``; W row-sharded;
                   returns activations sequence-sharded on ``axis``.

    A sequence length not divisible by the ``axis`` ring is padded with
    zeros up to the next multiple and the result sliced back — ragged
    serving shapes work for both kinds and both modes.
    """
    ag, rs = MODES[mode]
    bspec = batch_axis if batch_axis else None
    d = mesh.shape[axis]
    s = x.shape[1]
    s_pad = -(-s // d) * d
    if s_pad != s:
        x = jnp.pad(x, ((0, 0), (0, s_pad - s), (0, 0)))

    if kind == "column":
        def body(xl, wl):
            x2 = xl.reshape(-1, xl.shape[-1])
            y = ag(x2, wl, axis)
            # every shard holds the whole gathered sequence: drop the
            # padding here, where no sharding has to be resolved
            return y.reshape(xl.shape[0], -1, wl.shape[1])[:, :s]
        fn = jax.shard_map(body, mesh=mesh,
                           in_specs=(P(bspec, axis, None), P(None, axis)),
                           out_specs=P(bspec, None, axis), check_vma=False)
        return fn(x, w)
    elif kind == "row":
        def body(xl, wl):
            x2 = xl.reshape(-1, xl.shape[-1])
            y = rs(x2, wl, axis)
            return y.reshape(xl.shape[0], -1, wl.shape[1])
        fn = jax.shard_map(body, mesh=mesh,
                           in_specs=(P(bspec, None, axis), P(axis, None)),
                           out_specs=P(bspec, axis, None), check_vma=False)
        y = fn(x, w)
        if s_pad == s:
            return y
        # a ragged sequence cannot stay split over ``axis``
        return y.at[:, :s].get(
            out_sharding=NamedSharding(mesh, P(bspec, None, None)))
    raise ValueError(f"kind must be column|row, got {kind}")
