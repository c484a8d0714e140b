"""Decoder-LM assembly for every assigned family.

One parameter layout + three execution paths:
  * ``forward``      — full-sequence (train / prefill), scan over layers
  * ``decode_step``  — one token against per-layer caches (serve)
  * encoder-decoder  — whisper backbone (encode once, decode with cross-attn)

Layer parameters are *stacked* (leading ``L`` axis per leaf) and consumed by
``jax.lax.scan`` — constant-size HLO regardless of depth, which is what
keeps 96-layer × 512-way-sharded dry-run compiles tractable.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from .attention import (attn_init, attention_block, blocked_attention,
                        decode_attention, out_project, qkv_project)
from .common import (Params, apply_rope, cast_tree, dense_init, embed_init,
                     norm_apply, norm_init, sinusoidal_positions)
from .context import NULL_CTX, ModelContext
from .mlp import mlp_apply, mlp_init
from .moe import moe_apply_a2a, moe_apply_dense, moe_init
from .ssm import (mamba2_apply, mamba2_init, rwkv6_channel_mix,
                  rwkv6_channel_mix_init, rwkv6_init, rwkv6_time_mix)


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def _layer_init(cfg, key, dtype, moe_layer: bool) -> Params:
    k1, k2, k3, k4 = jax.random.split(key, 4)
    p: Params = {
        "ln1": norm_init(cfg.norm, cfg.d_model),
        "ln2": norm_init(cfg.norm, cfg.d_model),
        "attn": attn_init(k1, cfg.d_model, cfg.num_heads, cfg.num_kv_heads,
                          cfg.head_dim_, cfg.qkv_bias, dtype),
    }
    if moe_layer:
        p["moe"] = moe_init(k2, cfg.d_model, cfg.moe_num_experts,
                            cfg.moe_d_ff or cfg.d_ff,
                            cfg.moe_shared_experts, dtype)
    else:
        p["mlp"] = mlp_init(k3, cfg.d_model, cfg.d_ff, cfg.act, dtype)
    return p


def _stacked(init_fn, keys):
    return jax.vmap(init_fn)(keys)


def init_lm(cfg, key, dtype=jnp.float32) -> Params:
    """Parameters for any decoder-only family (dense/moe/ssm/hybrid/vlm)."""
    keys = jax.random.split(key, 8)
    p: Params = {"embed": embed_init(keys[0], cfg.vocab_size, cfg.d_model,
                                     dtype),
                 "ln_f": norm_init(cfg.norm, cfg.d_model)}
    if not cfg.tie_embeddings:
        p["lm_head"] = dense_init(keys[1], cfg.d_model, cfg.vocab_size, dtype)

    if cfg.family == "ssm":  # rwkv6
        lk = jax.random.split(keys[2], cfg.num_layers)
        p["layers"] = _stacked(
            lambda k: {
                "ln1": norm_init(cfg.norm, cfg.d_model),
                "ln2": norm_init(cfg.norm, cfg.d_model),
                "tmix": rwkv6_init(k, cfg.d_model, cfg.rwkv_head_dim, dtype),
                "cmix": rwkv6_channel_mix_init(
                    jax.random.fold_in(k, 1), cfg.d_model, cfg.d_ff, dtype),
            }, lk)
        return p

    if cfg.family == "hybrid":  # zamba2
        lk = jax.random.split(keys[2], cfg.num_layers)
        heads = cfg.ssm_heads or cfg.num_heads
        p["layers"] = _stacked(
            lambda k: {
                "ln": norm_init(cfg.norm, cfg.d_model),
                "mamba": mamba2_init(k, cfg.d_model, cfg.ssm_state, heads,
                                     cfg.ssm_expand, dtype),
            }, lk)
        p["shared_block"] = _layer_init(cfg, keys[3], dtype, moe_layer=False)
        p["shared_proj"] = dense_init(keys[4], 2 * cfg.d_model, cfg.d_model,
                                      dtype)
        return p

    moe_from = cfg.moe_first_dense if cfg.family == "moe" else cfg.num_layers
    n_dense = moe_from if cfg.family == "moe" else cfg.num_layers
    if cfg.family == "moe":
        if n_dense:
            dk = jax.random.split(keys[2], n_dense)
            p["dense_layers"] = _stacked(
                lambda k: _layer_init(cfg, k, dtype, moe_layer=False), dk)
        mk = jax.random.split(keys[3], cfg.num_layers - n_dense)
        p["layers"] = _stacked(
            lambda k: _layer_init(cfg, k, dtype, moe_layer=True), mk)
    else:
        lk = jax.random.split(keys[2], cfg.num_layers)
        p["layers"] = _stacked(
            lambda k: _layer_init(cfg, k, dtype, moe_layer=False), lk)

    if cfg.is_encoder_decoder:
        ek = jax.random.split(keys[5], cfg.encoder_layers)
        p["encoder_layers"] = _stacked(
            lambda k: _layer_init(cfg, k, dtype, moe_layer=False), ek)
        ck = jax.random.split(keys[6], cfg.num_layers)
        p["cross_attn"] = _stacked(
            lambda k: {"ln": norm_init(cfg.norm, cfg.d_model),
                       "attn": attn_init(k, cfg.d_model, cfg.num_heads,
                                         cfg.num_kv_heads, cfg.head_dim_,
                                         cfg.qkv_bias, dtype)}, ck)
        p["ln_enc"] = norm_init(cfg.norm, cfg.d_model)
    if cfg.frontend == "patch":
        p["patch_proj"] = dense_init(keys[7], cfg.d_model, cfg.d_model, dtype)
    return p


def _fit_chunk(t: int, chunk: int) -> int:
    """Largest power-of-two-ish chunk ≤ `chunk` dividing sequence length."""
    c = min(chunk, t)
    while t % c:
        c //= 2
    return max(c, 1)


# ---------------------------------------------------------------------------
# blocks
# ---------------------------------------------------------------------------

def _dense_block(layer: Params, x: jnp.ndarray, cfg, ctx: ModelContext,
                 positions, causal=True) -> jnp.ndarray:
    # Megatron-SP layout: the residual stream stays sequence-sharded; the
    # post-norm activations are gathered to full-seq for attention/MLP (the
    # constraint below is the allgather point; the residual constraint at
    # the end is the reduce-scatter point).
    h = norm_apply(cfg.norm, layer["ln1"], x)
    h = ctx.shard(h, "dp", None, None)
    a = attention_block(layer["attn"], h, cfg, positions=positions,
                        causal=causal, block_q=ctx.block_q,
                        block_k=ctx.block_k, unroll=ctx.full_unroll)
    x = x + a
    x = ctx.shard(x, "dp", "sp", None)
    h = norm_apply(cfg.norm, layer["ln2"], x)
    h = ctx.shard(h, "dp", None, None)
    x = x + mlp_apply(layer["mlp"], h, cfg.act)
    return ctx.shard(x, "dp", "sp", None)


def _moe_block(layer: Params, x: jnp.ndarray, cfg, ctx: ModelContext,
               positions) -> Tuple[jnp.ndarray, jnp.ndarray]:
    h = norm_apply(cfg.norm, layer["ln1"], x)
    h = ctx.shard(h, "dp", None, None)
    a = attention_block(layer["attn"], h, cfg, positions=positions,
                        causal=True, block_q=ctx.block_q, block_k=ctx.block_k,
                        unroll=ctx.full_unroll)
    x = x + a
    x = ctx.shard(x, "dp", "sp", None)
    h = norm_apply(cfg.norm, layer["ln2"], x)
    h = ctx.shard(h, "dp", None, None)
    seq_shardable = (ctx.mesh is not None and ctx.ep_axis is not None
                     and h.shape[1] % ctx.mesh.shape[ctx.ep_axis] == 0)
    if seq_shardable:
        # Expert parallelism with SEQUENCE-sharded dispatch: each EP peer
        # routes its own 1/ep slice of the tokens and the AlltoAll moves
        # only real work (replicated dispatch would cost ep× redundant
        # expert FLOPs — see EXPERIMENTS.md §Perf iteration 1).
        from jax.sharding import PartitionSpec as P
        dp = ctx.axes.get("dp")
        espec = P(ctx.ep_axis, None, ctx.ep_tp_axis) \
            if ctx.ep_tp_axis else P(ctx.ep_axis, None, None)
        dspec = P(ctx.ep_axis, ctx.ep_tp_axis, None) \
            if ctx.ep_tp_axis else P(ctx.ep_axis, None, None)
        shared_spec = {}
        if "shared" in layer["moe"]:
            up = P(None, ctx.ep_tp_axis) if ctx.ep_tp_axis else P(None, None)
            dn = P(ctx.ep_tp_axis, None) if ctx.ep_tp_axis else P(None, None)
            shared_spec = {"w_up": up, "w_gate": up, "w_down": dn}
        in_specs = ({"router": P(None, None),
                     "w_up": espec, "w_gate": espec, "w_down": dspec,
                     **({"shared": shared_spec} if shared_spec else {})},
                    P(dp, ctx.ep_axis, None))
        moe_fn = jax.shard_map(
            lambda mp, xx: moe_apply_a2a(mp, xx, cfg, ep_axis=ctx.ep_axis,
                                         tp_axis=ctx.ep_tp_axis,
                                         mean_axes=ctx.mesh.axis_names),
            mesh=ctx.mesh, in_specs=in_specs,
            out_specs=(P(dp, ctx.ep_axis, None), P()), check_vma=False)
        y, aux = moe_fn(layer["moe"], h)
    else:
        y, aux = moe_apply_dense(layer["moe"], h, cfg)
    x = x + y
    return ctx.shard(x, "dp", "sp", None), aux


# ---------------------------------------------------------------------------
# forward (train / prefill)
# ---------------------------------------------------------------------------

def forward(params: Params, cfg, tokens: jnp.ndarray, *,
            ctx: ModelContext = NULL_CTX,
            patch_embeds: Optional[jnp.ndarray] = None,
            frame_embeds: Optional[jnp.ndarray] = None,
            positions: Optional[jnp.ndarray] = None) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """tokens (B, S) -> (logits (B, S, V), aux_loss scalar)."""
    b, s = tokens.shape
    compute_dtype = jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32
    x = jnp.take(params["embed"], tokens, axis=0).astype(compute_dtype)
    if positions is None:
        positions = jnp.arange(s)[None, :]
    if cfg.frontend == "patch" and patch_embeds is not None:
        pe = jnp.einsum("bpd,de->bpe", patch_embeds.astype(compute_dtype),
                        params["patch_proj"].astype(compute_dtype))
        npatch = pe.shape[1]
        x = jnp.concatenate([pe, x[:, npatch:]], axis=1)
    x = ctx.shard(x, "dp", "sp", None)
    aux_total = jnp.zeros((), jnp.float32)

    def _scan(body, carry, xs, length=None):
        n = length if length is not None else jax.tree_util.tree_leaves(xs)[0].shape[0]
        return jax.lax.scan(body, carry, xs,
                            unroll=n if ctx.full_unroll else 1)

    if cfg.family == "ssm":
        def body(carry, lp):
            h = carry
            y, _ = rwkv6_time_mix(lp["tmix"],
                                  norm_apply(cfg.norm, lp["ln1"], h),
                                  cfg.rwkv_head_dim,
                                  chunk=_fit_chunk(s, ctx.ssm_chunk),
                                  unroll=ctx.full_unroll)
            h = h + y
            y, _ = rwkv6_channel_mix(lp["cmix"],
                                     norm_apply(cfg.norm, lp["ln2"], h))
            h = h + y
            return ctx.shard(h, "dp", "sp", None), None
        x, _ = _scan(ctx.maybe_remat(body), x, params["layers"])

    elif cfg.family == "hybrid":
        heads = cfg.ssm_heads or cfg.num_heads
        k = cfg.attn_every
        ngroups = cfg.num_layers // k
        stk = jax.tree_util.tree_map(
            lambda a: a.reshape(ngroups, k, *a.shape[1:]), params["layers"])
        shared = params["shared_block"]
        sproj = params["shared_proj"]
        x0 = x  # zamba: shared block sees concat(x, x0)

        def group(carry, glayers):
            h = carry

            def mamba_body(hh, lp):
                y, _ = mamba2_apply(lp["mamba"],
                                    norm_apply(cfg.norm, lp["ln"], hh),
                                    heads, cfg.ssm_state, cfg.ssm_expand,
                                    chunk=_fit_chunk(s, ctx.ssm_chunk),
                                    unroll=ctx.full_unroll)
                return ctx.shard(hh + y, "dp", "sp", None), None
            h, _ = _scan(ctx.maybe_remat(mamba_body), h, glayers)
            # shared attention block on concat(h, x0) -> project back
            cat = jnp.concatenate([h, x0], axis=-1)
            z = jnp.einsum("bsd,de->bse", cat, sproj.astype(cat.dtype))
            z = _dense_block(shared, z, cfg, ctx, positions)
            return ctx.shard(h + z, "dp", "sp", None), None
        x, _ = _scan(group, x, stk)

    elif cfg.family == "moe":
        if "dense_layers" in params:
            def dbody(carry, lp):
                return _dense_block(lp, carry, cfg, ctx, positions), None
            x, _ = _scan(ctx.maybe_remat(dbody), x, params["dense_layers"])

        def mbody(carry, lp):
            h, aux = carry
            h, a = _moe_block(lp, h, cfg, ctx, positions)
            return (h, aux + a), None
        (x, aux_total), _ = _scan(ctx.maybe_remat(mbody),
                                  (x, aux_total), params["layers"])

    elif cfg.is_encoder_decoder:
        assert frame_embeds is not None, "audio family needs frame embeddings"
        enc = frame_embeds.astype(compute_dtype)
        enc = enc + sinusoidal_positions(enc.shape[1], cfg.d_model
                                         ).astype(compute_dtype)[None]
        enc = ctx.shard(enc, "dp", "sp", None)

        def ebody(carry, lp):
            return _dense_block(lp, carry, cfg, ctx, positions=None,
                                causal=False), None
        enc, _ = _scan(ctx.maybe_remat(ebody), enc,
                       params["encoder_layers"])
        enc = norm_apply(cfg.norm, params["ln_enc"], enc)
        hq, hkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim_

        def dbody(carry, lp):
            layer, xlayer = lp
            h = carry
            h = _dense_block(layer, h, cfg, ctx, positions)
            cn = norm_apply(cfg.norm, xlayer["ln"], h)
            q, k_, v_ = qkv_project(xlayer["attn"], cn, hq, hkv, hd)
            ek, ev = qkv_project(xlayer["attn"], enc, hq, hkv, hd)[1:]
            o = blocked_attention(q, ek, ev, causal=False,
                                  block_q=ctx.block_q, block_k=ctx.block_k,
                                  unroll=ctx.full_unroll)
            h = h + out_project(xlayer["attn"], o)
            return ctx.shard(h, "dp", "sp", None), None
        x, _ = _scan(ctx.maybe_remat(dbody), x,
                     (params["layers"], params["cross_attn"]))

    else:  # dense / vlm
        def body(carry, lp):
            return _dense_block(lp, carry, cfg, ctx, positions), None
        x, _ = _scan(ctx.maybe_remat(body), x, params["layers"])

    x = norm_apply(cfg.norm, params["ln_f"], x)
    head = (params["embed"].T if cfg.tie_embeddings
            else params["lm_head"])
    logits = jnp.einsum("bsd,dv->bsv", x, head.astype(x.dtype))
    logits = ctx.shard(logits, "dp", None, "tp")
    return logits, aux_total


def lm_loss(params: Params, cfg, tokens: jnp.ndarray,
            labels: jnp.ndarray, *, ctx: ModelContext = NULL_CTX,
            aux_weight: float = 0.01, **kwargs) -> Tuple[jnp.ndarray, Dict]:
    logits, aux = forward(params, cfg, tokens, ctx=ctx, **kwargs)
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = (logz - gold).mean()
    loss = nll + aux_weight * aux
    return loss, {"nll": nll, "aux": aux}
