"""Block composition and full-model forward passes for all 10 architectures.

Layer stacking uses `jax.lax.scan` over parameter pytrees stacked on a
leading L axis — HLO size (and XLA compile time) stays independent of depth,
which is what makes the 48-60-layer production configs compilable in the
dry-run. Heterogeneous archs scan over their repeating unit:

  dense / vlm / audio : scan over L identical (attn + MLP) blocks
  deepseek-v2         : 1 unscanned dense block + scan over 59 MLA+MoE blocks
  llama4-maverick     : scan over 24 (attn+MLP, attn+MoE) pairs (interleaved)
  rwkv6               : scan over 32 RWKV blocks
  zamba2              : scan over 6 super-blocks [6 Mamba2 + shared attn+MLP]
                        + a scanned tail of 2 Mamba2 blocks; the shared
                        block's weights are reused at every invocation
                        (per-invocation KV caches, stacked on the superblock
                        axis)

Activation checkpointing: cfg.remat == 'block' wraps each scanned body in
jax.checkpoint so the backward pass recomputes block internals.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models import attention, layers, mamba, moe, rwkv


def _split_stack(key, n, init_fn):
    return jax.vmap(init_fn)(jax.random.split(key, n))


def _maybe_remat(fn, cfg):
    if cfg.remat == "block":
        return jax.checkpoint(fn)
    return fn


def _scan_layers(body, carry, xs, cfg):
    """lax.scan over stacked layer params — or an unrolled python loop when
    cfg.unroll_layers (used by the dry-run's per-layer cost probes, since
    XLA's cost model counts a while body once regardless of trip count)."""
    if not cfg.unroll_layers:
        return jax.lax.scan(_maybe_remat(body, cfg), carry, xs)
    n = jax.tree.leaves(xs)[0].shape[0]
    ys = []
    for i in range(n):
        carry, y = body(carry, jax.tree.map(lambda a: a[i], xs))
        ys.append(y)
    if all(y is None for y in ys):
        return carry, None
    return carry, jax.tree.map(lambda *a: jnp.stack(a), *ys)


# --------------------------------------------------------------------------
# Standard transformer block (attn or MLA, MLP or MoE)
# --------------------------------------------------------------------------

def init_attn_block(key, cfg, ffn="mlp"):
    k1, k2 = jax.random.split(key)
    p = {"ln1": layers.init_rmsnorm(cfg.d_model),
         "ln2": layers.init_rmsnorm(cfg.d_model)}
    p["attn"] = (attention.init_mla(k1, cfg) if cfg.use_mla
                 else attention.init_gqa(k1, cfg))
    if ffn == "mlp":
        d_ff = cfg.dense_d_ff or cfg.d_ff
        p["mlp"] = layers.init_mlp(k2, cfg.d_model, d_ff, layers.dtype_of(cfg))
    else:
        p["moe"] = moe.init_moe(k2, cfg)
    return p


def attn_block_prefill(p, cfg, x, positions, ffn="mlp", gate_fn="softmax",
                       q_chunk=1024, kv_chunk=1024):
    xn = layers.rms_norm(p["ln1"], x, cfg.norm_eps)
    attn_fn = attention.mla_prefill if cfg.use_mla else attention.gqa_prefill
    x = x + attn_fn(p["attn"], cfg, xn, positions,
                    q_chunk=q_chunk, kv_chunk=kv_chunk)
    xn = layers.rms_norm(p["ln2"], x, cfg.norm_eps)
    if ffn == "mlp":
        return x + layers.mlp(p["mlp"], xn, cfg.act, cfg), None
    h, aux = moe.moe_apply(p["moe"], cfg, xn, gate_fn)
    return x + h, aux


def attn_block_decode(p, cfg, x, cache, pos, ffn="mlp", gate_fn="softmax"):
    xn = layers.rms_norm(p["ln1"], x, cfg.norm_eps)
    if cfg.use_mla:
        h, new_cache = attention.mla_decode(p["attn"], cfg, xn, cache, pos)
    else:
        h, new_cache = attention.gqa_decode(p["attn"], cfg, xn, cache, pos)
    x = x + h
    xn = layers.rms_norm(p["ln2"], x, cfg.norm_eps)
    if ffn == "mlp":
        x = x + layers.mlp(p["mlp"], xn, cfg.act, cfg)
    else:
        h, _ = moe.moe_apply(p["moe"], cfg, xn, gate_fn)
        x = x + h
    return x, new_cache


# --------------------------------------------------------------------------
# Architecture bodies: init + prefill/train forward + decode forward
# --------------------------------------------------------------------------

def init_body(key, cfg):
    fam = cfg.family
    if cfg.block == "rwkv":
        return {"blocks": _split_stack(
            key, cfg.num_layers, lambda k: rwkv.init_rwkv_block(k, cfg))}
    if cfg.block == "mamba":
        return _init_zamba_body(key, cfg)
    if cfg.moe and cfg.moe_layer_step > 1:      # llama4: interleaved pairs
        n_pairs = cfg.num_layers // cfg.moe_layer_step
        k1, k2 = jax.random.split(key)
        return {
            "pairs_dense": _split_stack(
                k1, n_pairs, lambda k: init_attn_block(k, cfg, "mlp")),
            "pairs_moe": _split_stack(
                k2, n_pairs, lambda k: init_attn_block(k, cfg, "moe")),
        }
    if cfg.moe:                                  # deepseek-v2: dense prefix
        k1, k2 = jax.random.split(key)
        n_moe = cfg.num_layers - cfg.first_k_dense
        return {
            "dense_prefix": _split_stack(
                k1, max(cfg.first_k_dense, 1),
                lambda k: init_attn_block(k, cfg, "mlp")),
            "moe_blocks": _split_stack(
                k2, n_moe, lambda k: init_attn_block(k, cfg, "moe")),
        }
    return {"blocks": _split_stack(
        key, cfg.num_layers, lambda k: init_attn_block(k, cfg, "mlp"))}


def _init_zamba_body(key, cfg):
    n_super = cfg.num_layers // cfg.shared_attn_every if \
        cfg.shared_attn_every else 0
    per_super = cfg.shared_attn_every
    tail = cfg.num_layers - n_super * per_super
    k1, k2, k3 = jax.random.split(key, 3)
    p = {"mamba_super": _split_stack(
        k1, max(n_super, 1),
        lambda k: _split_stack(k, per_super or 1,
                               lambda kk: mamba.init_mamba_block(kk, cfg)))}
    if tail:
        p["mamba_tail"] = _split_stack(
            k2, tail, lambda k: mamba.init_mamba_block(k, cfg))
    if cfg.shared_attn_every:
        p["shared_attn"] = init_attn_block(k3, cfg, "mlp")
    return p


# ---- prefill / train forward ----------------------------------------------

def body_prefill(params, cfg, x, positions, q_chunk=1024, kv_chunk=1024):
    """x: (B,S,d) -> (B,S,d), aux. Scan-over-layers everywhere.

    aux["loss"] is the router load-balancing loss (0 without MoE); MoE
    archs add `held_assignments` and `largest_held_group`, one entry per
    MoE layer (`moe.moe_apply`)."""
    aux_total = jnp.float32(0.0)
    no_moe = {"loss": aux_total}
    gate_fn = "sigmoid" if cfg.moe_layer_step > 1 else "softmax"

    if cfg.block == "rwkv":
        state = rwkv.init_rwkv_state(cfg, x.shape[0], x.dtype)

        def body(h, blk):
            out, _ = rwkv.rwkv_block(blk, cfg, h, state)
            return out, None
        x, _ = _scan_layers(body, x, params["blocks"], cfg)
        return x, no_moe

    if cfg.block == "mamba":
        return _zamba_prefill(params, cfg, x, positions, q_chunk, kv_chunk)

    if cfg.moe and cfg.moe_layer_step > 1:
        def pair_body(carry, blks):
            h, aux = carry
            dense_p, moe_p = blks
            h, _ = attn_block_prefill(dense_p, cfg, h, positions, "mlp",
                                      q_chunk=q_chunk, kv_chunk=kv_chunk)
            h, a = attn_block_prefill(moe_p, cfg, h, positions, "moe",
                                      gate_fn, q_chunk, kv_chunk)
            loss = a.pop("loss")
            return (h, aux + loss), a
        (x, aux_total), counts = _scan_layers(
            pair_body, (x, aux_total),
            (params["pairs_dense"], params["pairs_moe"]), cfg)
        return x, {"loss": aux_total, **counts}

    if cfg.moe:
        def dense_body(carry, blk):
            h, _ = attn_block_prefill(blk, cfg, carry, positions, "mlp",
                                      q_chunk=q_chunk, kv_chunk=kv_chunk)
            return h, None
        x, _ = _scan_layers(dense_body, x, params["dense_prefix"], cfg)

        def moe_body(carry, blk):
            h, aux = carry
            h, a = attn_block_prefill(blk, cfg, h, positions, "moe",
                                      gate_fn, q_chunk, kv_chunk)
            loss = a.pop("loss")
            return (h, aux + loss), a
        (x, aux_total), counts = _scan_layers(
            moe_body, (x, aux_total), params["moe_blocks"], cfg)
        return x, {"loss": aux_total, **counts}

    def body(h, blk):
        out, _ = attn_block_prefill(blk, cfg, h, positions, "mlp",
                                    q_chunk=q_chunk, kv_chunk=kv_chunk)
        return out, None
    x, _ = _scan_layers(body, x, params["blocks"], cfg)
    return x, no_moe


def _zamba_prefill(params, cfg, x, positions, q_chunk, kv_chunk):
    state = mamba.init_mamba_state(cfg, x.shape[0], x.dtype)
    shared = params.get("shared_attn")

    def super_body(h, super_blks):
        def inner(hh, blk):
            out, _ = mamba.mamba_block(blk, cfg, hh, state)
            return out, None
        h, _ = jax.lax.scan(inner, h, super_blks)
        if shared is not None:
            h, _ = attn_block_prefill(shared, cfg, h, positions, "mlp",
                                      q_chunk=q_chunk, kv_chunk=kv_chunk)
        return h, None

    x, _ = _scan_layers(super_body, x, params["mamba_super"], cfg)
    if "mamba_tail" in params:
        def tail_body(h, blk):
            out, _ = mamba.mamba_block(blk, cfg, h, state)
            return out, None
        x, _ = _scan_layers(tail_body, x, params["mamba_tail"], cfg)
    return x, {"loss": jnp.float32(0.0)}


# ---- decode forward --------------------------------------------------------

def body_decode(params, cfg, x, caches, pos):
    """x: (B,1,d); caches as produced by init_caches. Returns (x, caches)."""
    gate_fn = "sigmoid" if cfg.moe_layer_step > 1 else "softmax"

    if cfg.block == "rwkv":
        def body(h, blk_cache):
            blk, st = blk_cache
            out, new_st = rwkv.rwkv_block(blk, cfg, h, st)
            return out, new_st
        x, new_states = _scan_layers(body, x,
                                     (params["blocks"], caches["blocks"]), cfg)
        return x, {"blocks": new_states}

    if cfg.block == "mamba":
        return _zamba_decode(params, cfg, x, caches, pos)

    if cfg.moe and cfg.moe_layer_step > 1:
        def pair_body(h, xs):
            dense_p, moe_p, c_d, c_m = xs
            h, nc_d = attn_block_decode(dense_p, cfg, h, c_d, pos, "mlp")
            h, nc_m = attn_block_decode(moe_p, cfg, h, c_m, pos, "moe",
                                        gate_fn)
            return h, (nc_d, nc_m)
        x, (nc_d, nc_m) = _scan_layers(
            pair_body, x, (params["pairs_dense"], params["pairs_moe"],
                           caches["dense"], caches["moe"]), cfg)
        return x, {"dense": nc_d, "moe": nc_m}

    if cfg.moe:
        def dense_body(h, xs):
            blk, c = xs
            h, nc = attn_block_decode(blk, cfg, h, c, pos, "mlp")
            return h, nc
        x, nc_prefix = _scan_layers(
            dense_body, x, (params["dense_prefix"], caches["dense_prefix"]), cfg)

        def moe_body(h, xs):
            blk, c = xs
            h, nc = attn_block_decode(blk, cfg, h, c, pos, "moe", gate_fn)
            return h, nc
        x, nc_moe = _scan_layers(
            moe_body, x, (params["moe_blocks"], caches["moe_blocks"]), cfg)
        return x, {"dense_prefix": nc_prefix, "moe_blocks": nc_moe}

    def body(h, xs):
        blk, c = xs
        h, nc = attn_block_decode(blk, cfg, h, c, pos, "mlp")
        return h, nc
    x, ncs = _scan_layers(body, x, (params["blocks"], caches["blocks"]), cfg)
    return x, {"blocks": ncs}


def _zamba_decode(params, cfg, x, caches, pos):
    shared = params.get("shared_attn")

    def super_body(h, xs):
        super_blks, m_state, attn_cache = xs

        def inner(carry, blk_state):
            hh = carry
            blk, st = blk_state
            out, new_st = mamba.mamba_block(blk, cfg, hh, st)
            return out, new_st
        h, new_m = jax.lax.scan(inner, h, (super_blks, m_state))
        if shared is not None:
            h, new_attn = attn_block_decode(shared, cfg, h, attn_cache, pos)
        else:
            new_attn = attn_cache
        return h, (new_m, new_attn)

    x, (new_m, new_attn) = _scan_layers(
        super_body, x,
        (params["mamba_super"], caches["mamba_super"], caches["shared_attn"]), cfg)
    out_caches = {"mamba_super": new_m, "shared_attn": new_attn}
    if "mamba_tail" in params:
        def tail_body(h, xs):
            blk, st = xs
            out, new_st = mamba.mamba_block(blk, cfg, h, st)
            return out, new_st
        x, new_tail = _scan_layers(
            tail_body, x, (params["mamba_tail"], caches["mamba_tail"]), cfg)
        out_caches["mamba_tail"] = new_tail
    return x, out_caches
