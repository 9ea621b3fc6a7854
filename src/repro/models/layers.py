"""Shared building blocks: parameter maker, norms, RoPE, activations."""
from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .sharding import MeshRules

ACT = {"silu": jax.nn.silu, "gelu": jax.nn.gelu, "relu": jax.nn.relu}


class Maker:
    """Builds the parameter pytree either as real arrays (``init``) or as
    ShapeDtypeStructs with shardings attached (``abstract`` — used by the
    dry-run so no host allocation ever happens)."""

    def __init__(self, mode: str, rules: MeshRules, dtype,
                 key: Optional[jax.Array] = None):
        assert mode in ("init", "abstract")
        self.mode = mode
        self.rules = rules
        self.dtype = dtype
        self._key = key
        self._counter = 0

    def param(self, shape: Sequence[int], logical: Sequence[Optional[str]],
              scale: Optional[float] = None, zeros: bool = False,
              dtype=None) -> jax.Array:
        shape = tuple(int(s) for s in shape)
        dtype = dtype or self.dtype
        assert len(shape) == len(logical), (shape, logical)
        sharding = self.rules.fitted_sharding(shape, *logical)
        if self.mode == "abstract":
            if sharding is not None:
                return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
            return jax.ShapeDtypeStruct(shape, dtype)
        self._counter += 1
        if zeros:
            arr = jnp.zeros(shape, dtype)
        else:
            k = jax.random.fold_in(self._key, self._counter)
            if scale is None:
                fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
                scale = 1.0 / np.sqrt(max(1, fan_in))
            arr = (jax.random.normal(k, shape, jnp.float32) * scale
                   ).astype(dtype)
        if sharding is not None:
            arr = jax.device_put(arr, sharding)
        return arr

    def ones(self, shape, logical, dtype=None):
        shape = tuple(int(s) for s in shape)
        dtype = dtype or self.dtype
        sharding = self.rules.fitted_sharding(shape, *logical)
        if self.mode == "abstract":
            if sharding is not None:
                return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
            return jax.ShapeDtypeStruct(shape, dtype)
        arr = jnp.ones(shape, dtype)
        if sharding is not None:
            arr = jax.device_put(arr, sharding)
        return arr


# ------------------------------------------------------------------ norms
def rms_norm(x: jax.Array, weight: Optional[jax.Array],
             eps: float = 1e-5) -> jax.Array:
    dt = x.dtype
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    if weight is not None:
        y = y * weight.astype(jnp.float32)
    return y.astype(dt)


def layer_norm(x: jax.Array, weight: Optional[jax.Array],
               bias: Optional[jax.Array], eps: float = 1e-5) -> jax.Array:
    """Supports OLMo's non-parametric LN (weight=bias=None)."""
    dt = x.dtype
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean((xf - mu) ** 2, axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    if weight is not None:
        y = y * weight.astype(jnp.float32)
    if bias is not None:
        y = y + bias.astype(jnp.float32)
    return y.astype(dt)


def apply_norm(cfg, x: jax.Array, p: Optional[jax.Array]) -> jax.Array:
    if cfg.nonparametric_ln:
        return layer_norm(x, None, None, cfg.norm_eps)
    return rms_norm(x, p, cfg.norm_eps)


# ------------------------------------------------------------------- RoPE
def rope_angles(positions: jax.Array, head_dim: int,
                theta: float) -> Tuple[jax.Array, jax.Array]:
    """positions: (...,) int -> cos/sin of shape (..., head_dim//2)."""
    half = head_dim // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = positions.astype(jnp.float32)[..., None] * freqs
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """x: (B, S, H, D); cos/sin: (B, S, D/2) or (S, D/2)."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    if cos.ndim == 2:        # (S, D/2) -> (1, S, 1, D/2)
        cos, sin = cos[None, :, None, :], sin[None, :, None, :]
    elif cos.ndim == 3:      # (B, S, D/2) -> (B, S, 1, D/2)
        cos, sin = cos[:, :, None, :], sin[:, :, None, :]
    xf1, xf2 = x1.astype(jnp.float32), x2.astype(jnp.float32)
    return jnp.concatenate(
        [xf1 * cos - xf2 * sin, xf2 * cos + xf1 * sin], axis=-1
    ).astype(x.dtype)


# §Perf toggle: row-parallel projections reduce their partial sums with
# an EXPLICIT bf16 psum (shard_map) instead of letting SPMD all-reduce
# the f32 dot partials — halves the dominant TP collective payload.
# (Within-shard accumulation stays f32 via preferred_element_type.)
BF16_ROW_PSUM = True


def row_parallel_matmul(x: jax.Array, w: jax.Array,
                        rules: MeshRules) -> jax.Array:
    """y = x @ w for w row-sharded on the model axis; psum in x.dtype."""
    ax = rules.model_axis
    n = rules.axis_size(ax)
    if (not BF16_ROW_PSUM or rules.mesh is None or n <= 1
            or x.ndim != 3 or x.shape[-1] % n or w.shape[0] % n):
        return x @ w
    from jax.sharding import PartitionSpec as P

    bspec = rules.physical("batch")

    def body(xl, wl):
        part = jnp.dot(xl, wl, preferred_element_type=jnp.float32)
        return jax.lax.psum(part.astype(x.dtype), ax)

    fn = jax.shard_map(body, mesh=rules.mesh,
                       in_specs=(P(bspec, None, ax), P(ax, None)),
                       out_specs=P(bspec, None, None), check_vma=False)
    return fn(x, w)


# ------------------------------------------------------------------- MLP
def make_mlp_params(mk: Maker, d: int, ff: int) -> dict:
    return {
        "wi": mk.param((d, ff), ("embed", "model")),
        "wg": mk.param((d, ff), ("embed", "model")),
        "wo": mk.param((ff, d), ("model", "embed")),
    }


def mlp(cfg, p: dict, x: jax.Array, rules: MeshRules) -> jax.Array:
    act = ACT[cfg.act]
    h = act(x @ p["wg"]) * (x @ p["wi"])
    h = rules.constrain(h, "batch", None, "model")
    return row_parallel_matmul(h, p["wo"], rules)
