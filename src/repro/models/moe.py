"""Mixture-of-Experts: dropless top-k routing over the experts a device
holds, and shared experts. Covers deepseek-v2 / v2-lite (softmax gates,
top-6 of 160 / 64 routed, 2 shared) and llama4-maverick (sigmoid gate,
top-1 of 128, 1 shared).

Every token is routed over all `num_experts` experts; the device computes
the part of the routed sum that its held experts give
(`held_experts_ffn`): experts [first, first + held), where `first` comes
from the config on one device (`first_held_expert`, the chip's share of an
expert-parallel deployment) and from the "model" mesh index under
shard_map, whose psum then adds the shares. Assignments to experts not
held add nothing here; no token is ever dropped.

Dispatch is a counting sort: each (token, expert) assignment to a held
expert gets its row in expert order (the expert's start plus its rank
among that expert's assignments), the token rows are gathered into that
order, the held experts' SwiGLU runs on their ragged row groups through
the grouped matmul (`kernels/moe_gmm`: Pallas on TPU, `ragged_dot`
elsewhere), and the combine sums each token's held rows with their
gates (`kernels/moe_combine`: on TPU a Pallas kernel that reads each token
tile's rows from every held expert's contiguous range, the slot-major jnp
gather elsewhere). FLOPs are the active N·k·held/E·d·ff
(plus the router); the static row buffer is the worst case, N·min(k,
held) rows, and a batch whose buffer would pass ROWS_MAX rows runs in
token blocks.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.moe_combine.ops import moe_combine
from repro.kernels.moe_gmm.ops import moe_gmm
from repro.models import layers
from repro.models.layers import dense_init

ROWS_MAX = 1 << 17      # dispatch rows of one token block


def init_moe(key, cfg):
    d, e, ff = cfg.d_model, cfg.num_experts, cfg.moe_d_ff
    dt = layers.dtype_of(cfg)
    ks = jax.random.split(key, 5)
    # Expert weights carry a leading axis of the held experts (shardable
    # over "model"); the router keeps all num_experts outputs.
    held = cfg.held_experts
    p = {
        "router": dense_init(ks[0], d, e, jnp.float32),
        "w_gate": _stacked_init(ks[1], held, d, ff, dt),
        "w_up": _stacked_init(ks[2], held, d, ff, dt),
        "w_down": _stacked_init(ks[3], held, ff, d, dt),
    }
    if cfg.num_shared_experts:
        p["shared"] = layers.init_mlp(
            ks[4], d, cfg.moe_d_ff * cfg.num_shared_experts, dt)
    return p


def _stacked_init(key, e, d_in, d_out, dt):
    keys = jax.random.split(key, e)
    return jax.vmap(
        lambda k: dense_init(k, d_in, d_out, dt))(keys)


def top_k_routing(router_logits, cfg, gate_fn="softmax"):
    """(N, E) logits -> (N, k) expert ids, their gates, all E gates (fp32).

    Gates are renormalised over the k only where `cfg.norm_topk_prob`,
    then multiplied by `cfg.routed_scaling_factor`."""
    logits = router_logits.astype(jnp.float32)
    gates_all = (jax.nn.softmax(logits, axis=-1) if gate_fn == "softmax"
                 else jax.nn.sigmoid(logits))
    gate_vals, expert_ids = jax.lax.top_k(gates_all, cfg.num_experts_per_tok)
    if cfg.norm_topk_prob:
        gate_vals = gate_vals / jnp.maximum(
            jnp.sum(gate_vals, axis=-1, keepdims=True), 1e-9)
    return expert_ids, gate_vals * cfg.routed_scaling_factor, gates_all


def moe_apply(p, cfg, x, gate_fn="softmax"):
    """x: (B, S, d) -> (B, S, d), aux.

    aux: `loss`, the Switch load-balancing loss; `held_assignments`, the
    (token, expert) assignments that reached held experts; and
    `largest_held_group`, the most assignments one held expert took.

    Under a production mesh with experts over "model" (shard_activations)
    the layer runs in shard_map: activations are replicated across
    "model" under TP, so every expert shard dispatches and combines its
    own experts locally and the only collective is one bf16 psum of the
    (N_local, d) partial outputs. Requires num_experts % model-axis == 0.
    """
    from repro.models import meshctx
    mesh = meshctx.current_mesh()
    if (cfg.shard_activations and mesh is not None
            and "model" in mesh.axis_names):
        m_size = dict(zip(mesh.axis_names, mesh.devices.shape))["model"]
        if m_size > 1 and cfg.num_experts % m_size == 0:
            out, aux = _moe_apply_shardmap(p, cfg, x, gate_fn, mesh)
            return _add_shared(p, cfg, x, out), aux
    b, s, d = x.shape
    out, aux = held_experts_ffn(
        x.reshape(b * s, d), p["router"], p["w_gate"], p["w_up"],
        p["w_down"], cfg=cfg, gate_fn=gate_fn, first=cfg.first_held_expert)
    return _add_shared(p, cfg, x, out.reshape(b, s, d)), aux


def _add_shared(p, cfg, x, routed):
    """The shared experts, which every device computes alike, added once."""
    out = routed.astype(jnp.float32)
    if cfg.num_shared_experts:
        out = out + layers.mlp(p["shared"], x, cfg.act,
                               cfg).astype(jnp.float32)
    return out.astype(x.dtype)


def held_experts_ffn(xt, router, wg, wu, wd, *, cfg, gate_fn, first):
    """xt: (N, d) -> (the held experts' part of the routed sum (N, d)
    fp32, aux). wg/wu/wd hold experts [first, first + held)."""
    n, d = xt.shape
    k = cfg.num_experts_per_tok
    with jax.named_scope("supg.moe.route"):
        logits = jnp.einsum("nd,de->ne", xt.astype(jnp.float32), router,
                            preferred_element_type=jnp.float32)
        expert_ids, gate_vals, gates_all = top_k_routing(logits, cfg,
                                                         gate_fn)
    block = _token_block(n, min(k, wg.shape[0]))
    w_gate_up = jnp.concatenate([wg, wu], axis=-1)

    def one_block(xs):
        return _held_block(*xs, w_gate_up, wd, first)

    out, sizes = jax.lax.map(one_block, (
        xt.reshape(n // block, block, d),
        expert_ids.reshape(n // block, block, k),
        gate_vals.reshape(n // block, block, k)))
    sizes = jnp.sum(sizes, axis=0)

    # Switch-style load-balancing aux loss.
    density = jnp.mean(jax.nn.one_hot(expert_ids[:, 0], cfg.num_experts,
                                      dtype=jnp.float32), axis=0)
    prob_mass = jnp.mean(gates_all, axis=0)
    aux = {"loss": cfg.num_experts * jnp.sum(density * prob_mass)
           * cfg.router_aux_coef,
           "held_assignments": jnp.sum(sizes),
           "largest_held_group": jnp.max(sizes)}
    return out.reshape(n, d), aux


def _token_block(n, rows_per_token):
    """Tokens a block: n halved until its dispatch rows fit ROWS_MAX."""
    t = n
    while t * rows_per_token > ROWS_MAX and t % 2 == 0:
        t //= 2
    return t


def _held_block(xt, expert_ids, gate_vals, w_gate_up, wd, first):
    """One token block through the held experts: (out (n, d) fp32, the
    assignments each held expert took (held,))."""
    n = xt.shape[0]
    k = expert_ids.shape[1]
    held = wd.shape[0]
    rows = n * min(k, held)
    with jax.named_scope("supg.moe.dispatch"):
        rel = expert_ids.reshape(n * k) - first
        mine = (rel >= 0) & (rel < held)
        onehot = (jnp.where(mine, rel, held)[:, None]
                  == jnp.arange(held)[None, :]).astype(jnp.int32)
        sizes = jnp.sum(onehot, axis=0)
        rank = jnp.sum((jnp.cumsum(onehot, axis=0) - 1) * onehot, axis=1)
        start = jnp.cumsum(sizes) - sizes
        pos = jnp.where(mine, start[jnp.clip(rel, 0, held - 1)] + rank,
                        rows)
        row_token = jnp.zeros((rows,), jnp.int32).at[pos].set(
            jnp.arange(n * k, dtype=jnp.int32) // k, mode="drop")
        xs = xt[row_token]
    gu = moe_gmm(xs, w_gate_up, sizes)
    g, u = jnp.split(gu, 2, axis=-1)
    h = jax.nn.silu(g.astype(jnp.float32)).astype(xt.dtype) * u
    y = moe_gmm(h, wd, sizes)
    with jax.named_scope("supg.moe.combine"):
        out = moe_combine(y, pos.reshape(n, k), gate_vals, row_token, sizes)
    return out, sizes


# ---------------------------------------------------------------------------
# Expert-parallel shard_map path (production meshes)
# ---------------------------------------------------------------------------

def _local_experts(x_loc, router, wg, wu, wd, *, cfg, gate_fn, dp_axes):
    """Per-device body: this "model" shard's experts, psum'd partials.

    x_loc: (B_loc, S, d) — the device's data shard, replicated over "model".
    wg/wu/wd: (E_loc, ...) — this model-shard's experts (FSDP pre-gathered).
    """
    b_loc, s, d = x_loc.shape
    first = cfg.first_held_expert + jax.lax.axis_index("model") * wg.shape[0]
    partial, aux = held_experts_ffn(
        x_loc.reshape(b_loc * s, d), router, wg, wu, wd, cfg=cfg,
        gate_fn=gate_fn, first=first)
    # THE collective: one bf16-width psum of the partial outputs.
    out = jax.lax.psum(partial.astype(x_loc.dtype), "model")
    # x and router are model-replicated, so every model shard routes
    # identically: the loss is pmean'd over the data axes only.
    all_axes = ("model",) + dp_axes
    aux = {"loss": jax.lax.pmean(aux["loss"], dp_axes),
           "held_assignments": jax.lax.psum(aux["held_assignments"],
                                            all_axes),
           "largest_held_group": jax.lax.pmax(aux["largest_held_group"],
                                              all_axes)}
    return out.reshape(b_loc, s, d), aux


def _moe_apply_shardmap(p, cfg, x, gate_fn, mesh):
    from jax.experimental.shard_map import shard_map
    from jax.sharding import PartitionSpec as P

    dp = tuple(a for a in mesh.axis_names if a in ("pod", "data"))
    dp_entry = dp if len(dp) > 1 else dp[0]

    # FSDP pre-gather: force expert weights to model-sharded-only layout so
    # the shard_map body sees whole (E_loc, d, ff) experts.
    wg = jax.lax.with_sharding_constraint(
        p["w_gate"], P("model", None, None))
    wu = jax.lax.with_sharding_constraint(p["w_up"], P("model", None, None))
    wd = jax.lax.with_sharding_constraint(
        p["w_down"], P("model", None, None))
    router = jax.lax.with_sharding_constraint(p["router"], P(None, None))

    body = functools.partial(_local_experts, cfg=cfg, gate_fn=gate_fn,
                             dp_axes=dp)
    return shard_map(
        body, mesh=mesh,
        in_specs=(P(dp_entry, None, None), P(None, None),
                  P("model", None, None), P("model", None, None),
                  P("model", None, None)),
        out_specs=(P(dp_entry, None, None), P()),
        check_rep=False,
    )(x, router, wg, wu, wd)
