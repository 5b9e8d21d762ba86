"""Mixture-of-Experts block: top-k routing, shared experts, and two expert
compute paths:

* ``local``  — all experts resident (smoke tests / no EP): sort-based
               dispatch into (E, C) capacity slots + batched expert matmul
               (or the moe_gmm Pallas kernel when tiles align);
* ``a2a``    — expert parallelism over the ``model`` mesh axis: the same
               capacity dispatch, then an all-to-all exchanging (E, C, d)
               send slots for (P·C, d) per local expert and the reverse on
               the way back.  Run inside shard_map (distributed/moe_ep.py
               wires the collective); this module provides the pure
               per-shard math so it is testable single-device.

Capacity semantics: per source shard, each expert accepts at most
C = ceil(T·k/E · capacity_factor) tokens (token-drop MoE, standard for
static-shape TPU dispatch).  Dropped assignments contribute zero and their
router weight is renormalized away.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..configs.base import ArchConfig, MoEConfig
from .layers import dense_init, init_mlp, mlp


def init_moe(key, cfg: ArchConfig):
    mo = cfg.moe
    d, dt = cfg.d_model, cfg.dtype_
    ks = jax.random.split(key, 5)
    p = {"router": dense_init(ks[0], d, mo.n_experts, jnp.float32),
         "experts": {
             "wi_gate": _expert_init(ks[1], mo.n_experts, d, mo.d_ff_expert, dt),
             "wi_up": _expert_init(ks[2], mo.n_experts, d, mo.d_ff_expert, dt),
             "wo": _expert_init(ks[3], mo.n_experts, mo.d_ff_expert, d, dt)}}
    if mo.n_shared_experts:
        p["shared"] = init_mlp(ks[4], d,
                               mo.d_ff_shared * mo.n_shared_experts, dt)
    return p


def _expert_init(key, e, d_in, d_out, dtype):
    return (jax.random.normal(key, (e, d_in, d_out), jnp.float32)
            / np.sqrt(d_in)).astype(dtype)


def capacity(T: int, mo: MoEConfig, n_src_shards: int = 1) -> int:
    c = int(np.ceil(T * mo.top_k / mo.n_experts * mo.capacity_factor))
    return max(8, -(-c // 8) * 8)  # round up to 8 for tiling


def route(params, x, mo: MoEConfig):
    """x: (T, d) → (weights (T, k), experts (T, k), router logits)."""
    logits = jnp.einsum("td,de->te", x.astype(jnp.float32),
                        params["router"])
    weights, experts = jax.lax.top_k(logits, mo.top_k)
    weights = jax.nn.softmax(weights, axis=-1)
    return weights.astype(x.dtype), experts, logits


def dispatch(x, experts, weights, E: int, C: int):
    """Scatter tokens into per-expert capacity slots.

    x: (T, d); experts/weights: (T, k).  Returns
      x_send: (E, C, d), slot_of: (T, k) int32 (E*C ⇒ dropped),
      kept_weights: (T, k).
    """
    T, k = experts.shape
    flat_e = experts.reshape(-1)                           # (T*k,)
    # position of each assignment within its expert, in (token, slot) order
    onehot = jax.nn.one_hot(flat_e, E, dtype=jnp.int32)    # (T*k, E)
    pos_in_e = jnp.cumsum(onehot, axis=0) - onehot         # exclusive
    pos = jnp.take_along_axis(pos_in_e, flat_e[:, None], axis=1)[:, 0]
    keep = pos < C
    slot = jnp.where(keep, flat_e * C + pos, E * C)        # E*C = dropped
    token_of = jnp.repeat(jnp.arange(T), k)
    x_send = jnp.zeros((E * C + 1, x.shape[1]), x.dtype)
    x_send = x_send.at[slot].set(x[token_of])              # dup slots impossible
    kept_w = weights * keep.reshape(T, k).astype(weights.dtype)
    return x_send[:-1].reshape(E, C, -1), slot.reshape(T, k), kept_w


def combine(y_recv, slot_of, kept_w, T: int):
    """Gather expert outputs back to tokens.  y_recv: (E, C, dv)."""
    E, C, dv = y_recv.shape
    flat = jnp.concatenate(
        [y_recv.reshape(E * C, dv), jnp.zeros((1, dv), y_recv.dtype)])
    k = slot_of.shape[1]
    picked = flat[slot_of.reshape(-1)].reshape(T, k, dv)
    return jnp.einsum("tkd,tk->td", picked, kept_w)


def expert_ffn(eparams, x_e, act="silu"):
    """Batched expert MLP.  x_e: (E_local, N, d) → (E_local, N, d)."""
    gate = jnp.einsum("end,edf->enf", x_e, eparams["wi_gate"])
    up = jnp.einsum("end,edf->enf", x_e, eparams["wi_up"])
    g = jax.nn.silu(gate) if act == "silu" else \
        jax.nn.gelu(gate, approximate=True)
    return jnp.einsum("enf,efd->end", g * up, eparams["wo"])


def moe_block_local(params, x, cfg: ArchConfig):
    """Single-shard MoE forward (all experts local).  x: (B, S, d)."""
    mo = cfg.moe
    B, S, d = x.shape
    xt = x.reshape(B * S, d)
    w, e, logits = route(params, xt, mo)
    C = capacity(B * S, mo)
    x_send, slot, kept_w = dispatch(xt, e, w, mo.n_experts, C)
    y = expert_ffn(params["experts"], x_send, cfg.act)
    out = combine(y, slot, kept_w, B * S)
    if mo.n_shared_experts:
        out = out + mlp(params["shared"], xt, cfg.act)
    aux = load_balance_loss(logits, e, mo)
    return out.reshape(B, S, d), aux


def moe_block_a2a(params, x, cfg: ArchConfig, axis: str):
    """Expert-parallel MoE forward inside shard_map over ``axis``.

    x: (B_l, S_l, d) local shard; params['experts'] leaves are the LOCAL
    slices (E_local, ...).  The all-to-alls are the paper's channel pattern:
    a striped shared_region of expert slots, one-sided writes in, one-sided
    reads back (DESIGN.md §3).
    """
    mo = cfg.moe
    P = jax.lax.axis_size(axis)
    E_local = mo.n_experts // P
    B, S, d = x.shape
    T = B * S
    xt = x.reshape(T, d)
    w, e, logits = route(params, xt, mo)
    C = capacity(T, mo)
    x_send, slot, kept_w = dispatch(xt, e, w, mo.n_experts, C)
    # (E, C, d) = (P, E_local, C, d) → a2a → (P_src, E_local, C, d) local
    x_send = x_send.reshape(P, E_local, C, d)
    x_recv = jax.lax.all_to_all(x_send, axis, split_axis=0, concat_axis=0,
                                tiled=False)               # (P, E_local, C, d)
    x_e = x_recv.transpose(1, 0, 2, 3).reshape(E_local, P * C, d)
    y_e = expert_ffn(params["experts"], x_e, cfg.act)
    y_recv = y_e.reshape(E_local, P, C, d).transpose(1, 0, 2, 3)
    y_send = jax.lax.all_to_all(y_recv, axis, split_axis=0, concat_axis=0,
                                tiled=False)
    out = combine(y_send.reshape(mo.n_experts, C, d), slot, kept_w, T)
    if mo.n_shared_experts:
        out = out + mlp(params["shared"], xt, cfg.act)
    aux = load_balance_loss(logits, e, mo)
    return out.reshape(B, S, d), aux


def load_balance_loss(logits, experts, mo: MoEConfig):
    """Switch-style auxiliary load-balance loss (fraction × probability)."""
    T = logits.shape[0]
    probs = jax.nn.softmax(logits, axis=-1)                # (T, E)
    frac = jnp.mean(
        jax.nn.one_hot(experts[:, 0], mo.n_experts, dtype=jnp.float32),
        axis=0)
    prob = jnp.mean(probs, axis=0)
    return mo.n_experts * jnp.sum(frac * prob)
