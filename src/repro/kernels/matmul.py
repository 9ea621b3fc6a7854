"""Pallas TPU tiled-matmul kernel — the tile-algorithm compute hot-spot.

This is the BLASX tile kernel adapted to the TPU memory hierarchy:
the paper's T x T tile living in GPU RAM becomes a (block_m, block_k) /
(block_k, block_n) VMEM working set streamed from HBM by ``BlockSpec``;
the paper's L1-cache reuse of the stationary C tile becomes the f32
VMEM accumulator that stays resident across the K-loop (output-
stationary blocking).  The MXU sees hardware-aligned (multiple-of-128)
matmul dims chosen by ``ops.matmul``.

An optional fused epilogue (bias + activation) implements the
transformer projections of the model zoo without a second HBM
round-trip.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


ACTIVATIONS = {
    None: lambda x: x,
    "none": lambda x: x,
    "relu": lambda x: jnp.maximum(x, 0.0),
    "gelu": jax.nn.gelu,
    "silu": jax.nn.silu,
    "tanh": jnp.tanh,
}


def _dot_precision(a_ref, b_ref):
    """Full-precision passes for f32 operands: Mosaic's default
    contract precision is not guaranteed to be f32 on the MXU.  Half
    precisions need no extra passes."""
    if jnp.float32 in (a_ref.dtype, b_ref.dtype):
        return jax.lax.Precision.HIGHEST
    return None


def _matmul_kernel(a_ref, b_ref, o_ref, acc_ref, *, n_k: int,
                   activation: Optional[str]):
    """Grid = (m_blocks, n_blocks, k_blocks); K is the innermost
    (fastest-varying) axis so the accumulator stays VMEM-resident."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(a_ref[...], b_ref[...],
                            preferred_element_type=jnp.float32,
                            precision=_dot_precision(a_ref, b_ref))

    @pl.when(k == n_k - 1)
    def _store():
        out = ACTIVATIONS[activation](acc_ref[...])
        o_ref[...] = out.astype(o_ref.dtype)


def _matmul_bias_kernel(a_ref, b_ref, bias_ref, o_ref, acc_ref, *, n_k: int,
                        activation: Optional[str]):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(a_ref[...], b_ref[...],
                            preferred_element_type=jnp.float32,
                            precision=_dot_precision(a_ref, b_ref))

    @pl.when(k == n_k - 1)
    def _store():
        out = acc_ref[...] + bias_ref[...].astype(jnp.float32)
        out = ACTIVATIONS[activation](out)
        o_ref[...] = out.astype(o_ref.dtype)


def matmul_pallas(a: jax.Array, b: jax.Array, bias: Optional[jax.Array],
                  *, block_m: int, block_n: int, block_k: int,
                  out_dtype, activation: Optional[str],
                  interpret: bool = False) -> jax.Array:
    """Raw pallas_call.  Requires M % block_m == N % block_n ==
    K % block_k == 0 (``ops.matmul`` pads)."""
    m, k = a.shape
    k2, n = b.shape
    assert k == k2, (a.shape, b.shape)
    assert m % block_m == 0 and n % block_n == 0 and k % block_k == 0
    n_k = k // block_k
    grid = (m // block_m, n // block_n, n_k)

    in_specs = [
        pl.BlockSpec((block_m, block_k), lambda i, j, kk: (i, kk)),
        pl.BlockSpec((block_k, block_n), lambda i, j, kk: (kk, j)),
    ]
    args = [a, b]
    if bias is not None:
        in_specs.append(pl.BlockSpec((1, block_n), lambda i, j, kk: (0, j)))
        args.append(bias.reshape(1, n))
        kernel = functools.partial(_matmul_bias_kernel, n_k=n_k,
                                   activation=activation)
    else:
        kernel = functools.partial(_matmul_kernel, n_k=n_k,
                                   activation=activation)

    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(*args)
