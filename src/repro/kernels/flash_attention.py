"""Pallas flash-attention kernel (online-softmax, causal, GQA).

The second compute hot-spot after the matmul: prefill attention at 32k
context.  The BLASX tile insight applies directly — the (block_q, d)
query tile is the stationary operand resident in VMEM (L1 tile cache);
K/V panels stream past it (the ring of tiles); the running (m, l, acc)
statistics are the cached partial result, so the S x S score matrix
never exists in HBM.  Causal block-skipping prunes the upper-triangle
tiles entirely (the tile-algebra triangle walks of Eq. 1c/1d).

Layout: q (BH, Sq, D), k/v (BH_kv, Sk, D); grid (BH, Sq/bq, Sk/bk),
K innermost so the VMEM carry lives across the K-walk.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  n_k: int, scale: float, causal: bool, block_q: int,
                  block_k: int, kv_len: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # causal: skip blocks entirely above the diagonal
    first_q = qi * block_q
    last_q = first_q + block_q - 1
    first_k = ki * block_k

    @pl.when(jnp.logical_or(not causal, last_q >= first_k))
    def _compute():
        q = q_ref[0].astype(jnp.float32)          # (bq, d)
        k = k_ref[0].astype(jnp.float32)          # (bk, d)
        v = v_ref[0].astype(jnp.float32)          # (bk, d)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        qpos = first_q + jax.lax.broadcasted_iota(jnp.int32,
                                                  (block_q, block_k), 0)
        kpos = first_k + jax.lax.broadcasted_iota(jnp.int32,
                                                  (block_q, block_k), 1)
        mask = kpos < kv_len                       # padding
        if causal:
            mask = jnp.logical_and(mask, kpos <= qpos)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]                        # (bq, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)                     # (bq, bk)
        corr = jnp.exp(m_prev - m_new)             # (bq, 1)
        l_ref[...] = corr * l_ref[...] + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(ki == n_k - 1)
    def _store():
        denom = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / denom).astype(o_ref.dtype)


def flash_attention_bhsd(q: jax.Array, k: jax.Array, v: jax.Array, *,
                         causal: bool = True,
                         scale: Optional[float] = None,
                         block_q: int = 128, block_k: int = 128,
                         interpret: bool = False) -> jax.Array:
    """q: (BH, Sq, D); k/v: (BHkv, Sk, D) with BH % BHkv == 0 (GQA)."""
    bh, sq, d = q.shape
    bh_kv, sk, _ = k.shape
    assert bh % bh_kv == 0, (bh, bh_kv)
    group = bh // bh_kv
    scale = scale if scale is not None else d ** -0.5

    def pad_to(x, blk, axis):
        rem = (-x.shape[axis]) % blk
        if rem == 0:
            return x
        widths = [(0, 0)] * x.ndim
        widths[axis] = (0, rem)
        return jnp.pad(x, widths)

    qp = pad_to(q, block_q, 1)
    kp = pad_to(k, block_k, 1)
    vp = pad_to(v, block_k, 1)
    sqp, skp = qp.shape[1], kp.shape[1]
    n_k = skp // block_k
    grid = (bh, sqp // block_q, n_k)

    out = pl.pallas_call(
        functools.partial(_flash_kernel, n_k=n_k, scale=scale,
                          causal=causal, block_q=block_q, block_k=block_k,
                          kv_len=sk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d),
                         lambda b, i, j, g=group: (b // g, j, 0)),
            pl.BlockSpec((1, block_k, d),
                         lambda b, i, j, g=group: (b // g, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sqp, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),   # running max
            pltpu.VMEM((block_q, 1), jnp.float32),   # running denom
            pltpu.VMEM((block_q, d), jnp.float32),   # output accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(qp, kp, vp)
    return out[:, :sq, :]


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, scale: Optional[float] = None,
                    block_q: int = 128, block_k: int = 128,
                    interpret: bool = False) -> jax.Array:
    """Convenience layout: q (B, Sq, H, D); k/v (B, Sk, Hkv, D)."""
    b, sq, h, d = q.shape
    hkv = k.shape[2]
    q2 = q.transpose(0, 2, 1, 3).reshape(b * h, sq, d)
    k2 = k.transpose(0, 2, 1, 3).reshape(b * hkv, k.shape[1], d)
    v2 = v.transpose(0, 2, 1, 3).reshape(b * hkv, v.shape[1], d)
    o = flash_attention_bhsd(q2, k2, v2, causal=causal, scale=scale,
                             block_q=block_q, block_k=block_k,
                             interpret=interpret)
    return o.reshape(b, h, sq, d).transpose(0, 2, 1, 3)
