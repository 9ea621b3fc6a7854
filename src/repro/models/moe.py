"""Mixture-of-Experts with capacity-based scatter dispatch.

Design notes (also in DESIGN.md §Arch-applicability): expert FFNs are
batched tile GEMMs — the closest LM analogue of the paper's variable-
workload task pool.  Dispatch is exact-topk with a fixed per-expert
capacity C = ceil(tokens * top_k * capacity_factor / E): tokens beyond
capacity are dropped (standard GShard semantics).  The (E, C, d)
buffers shard E over the "model" axis (expert parallelism); GSPMD
materializes the all-to-all at the scatter/gather boundaries.

FLOP cost scales with top_k (not n_experts) — crucial for an honest
roofline on the 256-expert DeepSeek config.
"""
from __future__ import annotations

import math
from typing import Tuple

import jax
import jax.numpy as jnp

from .layers import ACT, Maker
from .sharding import MeshRules


def make_moe_params(mk: Maker, cfg) -> dict:
    d, E, ff = cfg.d_model, cfg.n_experts, cfg.moe_d_ff
    p = {
        "router": mk.param((d, E), ("embed", None), dtype=jnp.float32),
        "w_gate": mk.param((E, d, ff), ("expert", "embed", None)),
        "w_up": mk.param((E, d, ff), ("expert", "embed", None)),
        "w_down": mk.param((E, ff, d), ("expert", None, "embed")),
    }
    if cfg.n_shared_experts:
        sff = cfg.moe_d_ff * cfg.n_shared_experts
        p["shared"] = {
            "wg": mk.param((d, sff), ("embed", "model")),
            "wi": mk.param((d, sff), ("embed", "model")),
            "wo": mk.param((sff, d), ("model", "embed")),
        }
    return p


def _positions_in_expert(flat_e: jax.Array, n_experts: int) -> jax.Array:
    """Rank of each (token, slot) within its expert via stable argsort —
    the slot index into the expert's capacity buffer."""
    n = flat_e.shape[0]
    order = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[order]
    # index within each expert segment
    idx = jnp.arange(n, dtype=jnp.int32)
    seg_start = jnp.searchsorted(sorted_e, jnp.arange(n_experts,
                                                      dtype=flat_e.dtype))
    pos_sorted = idx - seg_start[sorted_e]
    inv = jnp.argsort(order, stable=True)
    return pos_sorted[inv]


# Toggle for the §Perf hillclimb: expert-local dispatch (shard_map) vs
# the baseline global scatter.  The baseline lets GSPMD materialize and
# all-reduce the (E*C, d) buffer per layer; the sharded path keeps the
# dispatch entirely device-local (tokens are replicated across the
# model axis, experts are sharded over it) and pays ONE activation-sized
# psum per layer.
SHARDED_DISPATCH = True


def moe_block(cfg, p: dict, x: jax.Array, rules: MeshRules,
              ) -> Tuple[jax.Array, dict]:
    """x: (B, S, d) -> (y, aux) with load-balance metrics in aux."""
    E, K = cfg.n_experts, cfg.top_k
    model_n = rules.axis_size(rules.model_axis)
    if (SHARDED_DISPATCH and rules.mesh is not None and model_n > 1
            and E % model_n == 0):
        return _moe_block_sharded(cfg, p, x, rules)
    return _moe_block_dense(cfg, p, x, rules)


def _router(cfg, p, xt):
    logits = (xt.astype(jnp.float32) @ p["router"]).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_w, gate_idx = jax.lax.top_k(probs, cfg.top_k)
    gate_w = gate_w / (jnp.sum(gate_w, axis=-1, keepdims=True) + 1e-9)
    return probs, gate_w, gate_idx


def _aux(cfg, probs, gate_idx, keep):
    E, K = cfg.n_experts, cfg.top_k
    me = jnp.mean(probs, axis=0)
    ce = jnp.mean(jax.nn.one_hot(gate_idx, E, dtype=jnp.float32).sum(1),
                  axis=0)
    return {"moe_aux_loss": E * jnp.sum(me * ce) / K,
            "moe_drop_frac": 1.0 - jnp.mean(keep.astype(jnp.float32))}


def _shared_expert(cfg, p, xt):
    act = ACT[cfg.act]
    sp = p["shared"]
    return (act(xt @ sp["wg"]) * (xt @ sp["wi"])) @ sp["wo"]


def _moe_block_sharded(cfg, p: dict, x: jax.Array, rules: MeshRules,
                       ) -> Tuple[jax.Array, dict]:
    """Expert-parallel dispatch with zero cross-device data movement for
    the token buffers: every model-column holds the full (data-sharded)
    token block, scatters locally into ITS E/model_n experts' capacity
    buffers, computes, and contributes a partial (N_local, d) output —
    combined by a single psum over the model axis."""
    from jax.sharding import PartitionSpec as P

    B, S, d = x.shape
    E, K = cfg.n_experts, cfg.top_k
    N = B * S
    xt = x.reshape(N, d)
    probs, gate_w, gate_idx = _router(cfg, p, xt)

    model_ax = rules.model_axis
    model_n = rules.axis_size(model_ax)
    batch_phys = rules.physical("batch")
    data_n = rules.axis_size(batch_phys)
    n_local = N // data_n if N % data_n == 0 else N
    dspec = batch_phys if N % data_n == 0 else None
    C = int(math.ceil((n_local if dspec else N) * K
                      * cfg.capacity_factor / E))
    C = max(1, C)
    e_local = E // model_n

    def body(xl, gw, gi, w_gate, w_up, w_down):
        # xl: (n_loc, d) — replicated across the model axis
        # w_*: (e_local, ...) — this column's experts
        m_idx = jax.lax.axis_index(model_ax)
        lo = m_idx * e_local
        flat_e = gi.reshape(-1)
        pos = _positions_in_expert(flat_e, E)
        mine = (flat_e >= lo) & (flat_e < lo + e_local)
        keep = (pos < C) & mine
        local_e = jnp.where(mine, flat_e - lo, 0)
        dest = jnp.where(keep, local_e * C + pos, e_local * C)
        x_rep = jnp.repeat(xl, K, axis=0)
        buf = jnp.zeros((e_local * C, xl.shape[1]), xl.dtype
                        ).at[dest].add(x_rep, mode="drop")
        buf = buf.reshape(e_local, C, xl.shape[1])
        act = ACT[cfg.act]
        h = act(jnp.einsum("ecd,edf->ecf", buf, w_gate)) * \
            jnp.einsum("ecd,edf->ecf", buf, w_up)
        out_buf = jnp.einsum("ecf,efd->ecd", h, w_down).reshape(
            e_local * C, xl.shape[1])
        gathered = jnp.where(
            keep[:, None], out_buf[jnp.minimum(dest, e_local * C - 1)], 0.0)
        y = jnp.sum((gathered * gw.reshape(-1)[:, None]
                     ).reshape(-1, K, xl.shape[1]), axis=1)
        return jax.lax.psum(y.astype(xl.dtype), model_ax)

    fn = jax.shard_map(
        body, mesh=rules.mesh,
        in_specs=(P(dspec, None), P(dspec, None), P(dspec, None),
                  P(model_ax, None, None), P(model_ax, None, None),
                  P(model_ax, None, None)),
        out_specs=P(dspec, None),
        check_vma=False,
    )
    y = fn(xt, gate_w, gate_idx, p["w_gate"], p["w_up"], p["w_down"])
    if cfg.n_shared_experts:
        y = y + _shared_expert(cfg, p, xt)
    # aux computed on the replicated router outputs (keep == capacity
    # estimate only; exact drop accounting lives in the sharded body)
    pos = _positions_in_expert(gate_idx.reshape(-1), E)
    aux = _aux(cfg, probs, gate_idx, pos < C * model_n)
    return y.reshape(B, S, d).astype(x.dtype), aux


def _moe_block_dense(cfg, p: dict, x: jax.Array, rules: MeshRules,
                     ) -> Tuple[jax.Array, dict]:
    B, S, d = x.shape
    E, K = cfg.n_experts, cfg.top_k
    N = B * S
    xt = x.reshape(N, d)

    logits = (xt.astype(jnp.float32) @ p["router"]).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_w, gate_idx = jax.lax.top_k(probs, K)              # (N, K)
    gate_w = gate_w / (jnp.sum(gate_w, axis=-1, keepdims=True) + 1e-9)

    C = int(math.ceil(N * K * cfg.capacity_factor / E))
    C = max(1, min(C, N))

    flat_e = gate_idx.reshape(-1)                            # (N*K,)
    pos = _positions_in_expert(flat_e, E)                    # (N*K,)
    keep = pos < C
    dest = jnp.where(keep, flat_e * C + pos, E * C)          # OOB -> dropped

    x_rep = jnp.repeat(xt, K, axis=0)                        # (N*K, d)
    buf = jnp.zeros((E * C, d), x.dtype).at[dest].add(
        x_rep, mode="drop")
    buf = buf.reshape(E, C, d)
    buf = rules.constrain(buf, "expert", None, None)

    act = ACT[cfg.act]
    h = act(jnp.einsum("ecd,edf->ecf", buf, p["w_gate"])) * \
        jnp.einsum("ecd,edf->ecf", buf, p["w_up"])
    out_buf = jnp.einsum("ecf,efd->ecd", h, p["w_down"])
    out_buf = rules.constrain(out_buf, "expert", None, None)

    flat_out = out_buf.reshape(E * C, d)
    gathered = jnp.where(keep[:, None], flat_out[jnp.minimum(dest, E * C - 1)],
                         0.0)
    y = jnp.sum(
        (gathered * gate_w.reshape(-1)[:, None]).reshape(N, K, d), axis=1)

    if cfg.n_shared_experts:
        sp = p["shared"]
        y = y + (act(xt @ sp["wg"]) * (xt @ sp["wi"])) @ sp["wo"]

    # aux: GShard load-balance loss + stats
    me = jnp.mean(probs, axis=0)                             # (E,)
    ce = jnp.mean(
        jax.nn.one_hot(gate_idx, E, dtype=jnp.float32).sum(1), axis=0)
    aux_loss = E * jnp.sum(me * ce) / K
    dropped = 1.0 - jnp.mean(keep.astype(jnp.float32))
    return y.reshape(B, S, d).astype(x.dtype), {"moe_aux_loss": aux_loss,
                                                "moe_drop_frac": dropped}
