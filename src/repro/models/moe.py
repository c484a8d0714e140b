"""Mixture-of-Experts layer: top-k routing, shared experts, expert parallelism.

Two execution paths with identical semantics (cross-checked in tests):

  * ``moe_apply_dense`` — single-device / GSPMD path: capacity-based
    dispatch with scatter/gather, experts applied as one stacked einsum.
  * ``moe_apply_a2a``   — expert-parallel path for use *inside*
    ``jax.shard_map``: tokens are bucketed into per-expert capacity slots,
    exchanged with ``jax.lax.all_to_all`` over the EP mesh axis, processed
    by the local expert shard, and returned by the inverse all-to-all.
    This emits the pairwise AlltoAll traffic the paper's vClos scheduler
    certifies contention-free (§5.3 expert parallelism).

Routing: softmax top-k with renormalised gates, capacity dropping
(capacity_factor × T·k/E), and the standard load-balance aux loss.
"""

from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from .common import Params, dense_init


def moe_init(key, d_model: int, num_experts: int, d_ff_expert: int,
             num_shared: int, dtype=jnp.float32) -> Params:
    kr, ku, kg, kd, ks = jax.random.split(key, 5)
    p = {
        "router": dense_init(kr, d_model, num_experts, jnp.float32),
        # stacked expert weights (E, D, F) / (E, F, D), gated SiLU
        "w_up": jax.vmap(lambda k: dense_init(k, d_model, d_ff_expert, dtype))(
            jax.random.split(ku, num_experts)),
        "w_gate": jax.vmap(lambda k: dense_init(k, d_model, d_ff_expert, dtype))(
            jax.random.split(kg, num_experts)),
        "w_down": jax.vmap(lambda k: dense_init(k, d_ff_expert, d_model, dtype))(
            jax.random.split(kd, num_experts)),
    }
    if num_shared:
        from .mlp import mlp_init
        p["shared"] = mlp_init(ks, d_model, d_ff_expert * num_shared, "silu",
                               dtype)
    return p


# ---------------------------------------------------------------------------
# routing (shared by both paths)
# ---------------------------------------------------------------------------

def _route(router_w: jnp.ndarray, x_flat: jnp.ndarray, top_k: int,
           num_experts: int, capacity: int):
    """x_flat: (T, D). Returns (expert_idx (T,k), gates (T,k),
    slot (T,k) position within expert, keep (T,k) bool, aux_loss)."""
    logits = jnp.einsum("td,de->te", x_flat.astype(jnp.float32), router_w)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, expert_idx = jax.lax.top_k(probs, top_k)          # (T, k)
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    # load-balance aux loss (Switch-style)
    me = probs.mean(axis=0)                                   # (E,)
    onehot_top1 = jax.nn.one_hot(expert_idx[:, 0], num_experts)
    ce = onehot_top1.mean(axis=0)
    aux = num_experts * jnp.sum(me * ce)
    # position within expert across (T*k) dispatch slots, column-major so
    # earlier tokens win capacity
    flat_e = expert_idx.reshape(-1)                           # (T·k,)
    onehot = jax.nn.one_hot(flat_e, num_experts, dtype=jnp.int32)
    pos = jnp.cumsum(onehot, axis=0) - 1                      # (T·k, E)
    slot = jnp.take_along_axis(pos, flat_e[:, None], axis=1)[:, 0]
    slot = slot.reshape(expert_idx.shape)
    keep = slot < capacity
    return expert_idx, gates, slot, keep, aux


def _capacity(t_tokens: int, top_k: int, num_experts: int,
              factor: float) -> int:
    cap = int(math.ceil(t_tokens * top_k * factor / num_experts))
    return max(8, ((cap + 7) // 8) * 8)  # pad to 8 for clean tiling


def _expert_ffn(w_up, w_gate, w_down, h):
    """h: (E, C, D) with stacked expert weights (E, D, F)."""
    up = jnp.einsum("ecd,edf->ecf", h, w_up.astype(h.dtype))
    gate = jnp.einsum("ecd,edf->ecf", h, w_gate.astype(h.dtype))
    act = jax.nn.silu(gate) * up
    return jnp.einsum("ecf,efd->ecd", act, w_down.astype(h.dtype))


# ---------------------------------------------------------------------------
# dense path (single device / pure GSPMD)
# ---------------------------------------------------------------------------

def moe_apply_dense(params: Params, x: jnp.ndarray, cfg) -> Tuple[jnp.ndarray, jnp.ndarray]:
    b, s, d = x.shape
    e, k = cfg.moe_num_experts, cfg.moe_top_k
    x_flat = x.reshape(-1, d)
    t = x_flat.shape[0]
    cap = _capacity(t, k, e, cfg.moe_capacity_factor)
    expert_idx, gates, slot, keep, aux = _route(
        params["router"], x_flat, k, e, cap)
    # scatter tokens into (E*C, D); dropped tokens target a scratch row
    dst = jnp.where(keep, expert_idx * cap + slot, e * cap)   # (T, k)
    buf = jnp.zeros((e * cap + 1, d), x.dtype)
    tok_rep = jnp.repeat(x_flat[:, None, :], k, axis=1)       # (T, k, D)
    buf = buf.at[dst.reshape(-1)].add(tok_rep.reshape(-1, d))
    h = buf[:e * cap].reshape(e, cap, d)
    out = _expert_ffn(params["w_up"], params["w_gate"], params["w_down"], h)
    out_flat = jnp.concatenate(
        [out.reshape(e * cap, d), jnp.zeros((1, d), x.dtype)], axis=0)
    fetched = out_flat[dst.reshape(-1)].reshape(t, k, d)
    y = jnp.einsum("tkd,tk->td", fetched,
                   (gates * keep).astype(fetched.dtype))
    if "shared" in params:
        from .mlp import mlp_apply
        y = y + mlp_apply(params["shared"], x, "silu").reshape(-1, d)
    return y.reshape(b, s, d), aux


# ---------------------------------------------------------------------------
# expert-parallel path (inside shard_map)
# ---------------------------------------------------------------------------

def moe_apply_a2a(params: Params, x: jnp.ndarray, cfg, *,
                  ep_axis: str, tp_axis: Optional[str] = None,
                  mean_axes: Optional[Tuple[str, ...]] = None
                  ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Per-device MoE with explicit AlltoAll.  Must run inside shard_map.

    params["w_up"] etc. arrive pre-sharded: (E_local, D, F_local).
    x arrives (batch, seq)-sharded over (dp, ep): every EP peer dispatches a
    distinct token slice, so the AlltoAll carries only real work.
    """
    b, s, d = x.shape
    e, k = cfg.moe_num_experts, cfg.moe_top_k
    ep = jax.lax.axis_size(ep_axis)
    e_local = e // ep
    x_flat = x.reshape(-1, d)
    t = x_flat.shape[0]
    cap = _capacity(t, k, e, cfg.moe_capacity_factor)
    expert_idx, gates, slot, keep, aux = _route(
        params["router"], x_flat, k, e, cap)
    dst = jnp.where(keep, expert_idx * cap + slot, e * cap)
    buf = jnp.zeros((e * cap + 1, d), x.dtype)
    tok_rep = jnp.repeat(x_flat[:, None, :], k, axis=1)
    buf = buf.at[dst.reshape(-1)].add(tok_rep.reshape(-1, d))
    send = buf[:e * cap].reshape(ep, e_local * cap, d)
    # ---- AlltoAll: send[e] goes to expert shard e (paper §5.3 pattern) ----
    recv = jax.lax.all_to_all(send, ep_axis, split_axis=0, concat_axis=0,
                              tiled=False)
    # recv: (ep_src, e_local*cap, d) — tokens from every source shard
    h = recv.reshape(ep, e_local, cap, d).transpose(1, 0, 2, 3) \
            .reshape(e_local, ep * cap, d)
    out = _expert_ffn(params["w_up"], params["w_gate"], params["w_down"], h)
    if tp_axis is not None:
        out = jax.lax.psum(out, tp_axis)  # F sharded: partial sums
    out = out.reshape(e_local, ep, cap, d).transpose(1, 0, 2, 3) \
             .reshape(ep, e_local * cap, d)
    back = jax.lax.all_to_all(out, ep_axis, split_axis=0, concat_axis=0,
                              tiled=False)
    out_flat = jnp.concatenate(
        [back.reshape(e * cap, d), jnp.zeros((1, d), x.dtype)], axis=0)
    fetched = out_flat[dst.reshape(-1)].reshape(t, k, d)
    y = jnp.einsum("tkd,tk->td", fetched,
                   (gates * keep).astype(fetched.dtype))
    if "shared" in params:
        from .mlp import mlp_apply
        sh = mlp_apply(params["shared"], x, "silu")
        if tp_axis is not None:
            sh = jax.lax.psum(sh, tp_axis)
        y = y + sh.reshape(-1, d)
    if mean_axes:
        aux = jax.lax.pmean(aux, mean_axes)
    return y.reshape(b, s, d), aux
