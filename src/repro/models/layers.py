"""Foundational layers: norms, embeddings, MLPs, RoPE, initializers.

Parameters are plain nested dicts of jnp arrays (pytrees). Every layer is a
pair of functions `init_*(key, ...) -> params` and `apply(params, x) -> y`,
kept pure so pjit/shard_map/scan compose without a module framework.

dtype policy: parameters are stored in cfg.dtype (bf16 in production
configs); matmuls accumulate in fp32 via `preferred_element_type`; norms and
softmax always run in fp32.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np


def dtype_of(cfg):
    return jnp.dtype(cfg.dtype)


def truncated_normal(key, shape, scale, dtype):
    return (jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32)
            * scale).astype(dtype)


def dense_init(key, d_in, d_out, dtype, scale=None):
    scale = scale if scale is not None else 1.0 / np.sqrt(d_in)
    return truncated_normal(key, (d_in, d_out), scale, dtype)


def matmul(x, w):
    """fp32-accumulating matmul over the last dim of x."""
    return jnp.einsum("...d,df->...f", x, w,
                      preferred_element_type=jnp.float32).astype(x.dtype)


def matmul_rowparallel(x, w, cfg):
    """Row-parallel (partial-sum) matmul: under TP the output needs a
    cross-shard all-reduce. With shard_activations (production meshes) the
    local result is emitted in the model dtype so GSPMD's all-reduce moves
    bf16, not fp32 — halving the dominant TP wire bytes (§Perf it. 5). The
    MXU still accumulates each local product in fp32; only the <=16-term
    cross-shard sum runs at bf16 (standard Megatron bf16-reduce mode)."""
    if cfg is not None and cfg.shard_activations and x.dtype != jnp.float32:
        return jnp.einsum("...d,df->...f", x, w,
                          preferred_element_type=x.dtype)
    return matmul(x, w)


# --------------------------------------------------------------------------
# RMSNorm
# --------------------------------------------------------------------------

def init_rmsnorm(d):
    return {"scale": jnp.ones((d,), jnp.float32)}


def rms_norm(params, x, eps=1e-5):
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps) * params["scale"]
    return y.astype(x.dtype)


# --------------------------------------------------------------------------
# Embedding + output head
# --------------------------------------------------------------------------

def init_embedding(key, vocab, d, dtype):
    return {"table": truncated_normal(key, (vocab, d), 1.0, dtype)}


def embed(params, tokens):
    return jnp.take(params["table"], tokens, axis=0)


def unembed(params, x):
    """Logits via the (optionally tied) embedding table, fp32 accumulation."""
    return jnp.einsum("...d,vd->...v", x, params["table"],
                      preferred_element_type=jnp.float32)


def init_lm_head(key, d, vocab, dtype):
    return {"w": dense_init(key, d, vocab, dtype)}


def lm_head(params, x):
    return jnp.einsum("...d,dv->...v", x, params["w"],
                      preferred_element_type=jnp.float32)


# --------------------------------------------------------------------------
# Gated MLP (SwiGLU family)
# --------------------------------------------------------------------------

def init_mlp(key, d, d_ff, dtype):
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "w_gate": dense_init(k1, d, d_ff, dtype),
        "w_up": dense_init(k2, d, d_ff, dtype),
        "w_down": dense_init(k3, d_ff, d, dtype),
    }


def mlp(params, x, act="silu", cfg=None):
    g = matmul(x, params["w_gate"])
    u = matmul(x, params["w_up"])
    if act == "silu":
        h = jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * u
    elif act == "gelu":
        h = jax.nn.gelu(g.astype(jnp.float32)).astype(x.dtype) * u
    else:
        raise ValueError(act)
    return matmul_rowparallel(h, params["w_down"], cfg)


# --------------------------------------------------------------------------
# Rotary position embeddings
# --------------------------------------------------------------------------

def rope_frequencies(head_dim, theta):
    exponents = np.arange(0, head_dim, 2, dtype=np.float32) / head_dim
    return 1.0 / (theta ** exponents)  # (head_dim/2,)


def yarn_mscale(factor, mscale=1.0):
    """YaRN's attention temperature (DeepSeek-V2's `yarn_get_mscale`)."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_frequencies(head_dim, theta, factor, original_max, beta_fast,
                     beta_slow):
    """YaRN's inverse frequencies, as DeepSeek-V2's
    `DeepseekV2YarnRotaryEmbedding` blends them: dims that turn more than
    `beta_fast` times over the original context keep theta's frequency,
    dims that turn fewer than `beta_slow` times are divided by `factor`,
    and a linear ramp joins the two."""
    def correction_dim(rotations):
        return (head_dim * math.log(original_max / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))
    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), head_dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(head_dim // 2, dtype=np.float32) - low)
                   / (high - low), 0.0, 1.0)
    extra = rope_frequencies(head_dim, theta)
    return (extra / factor * ramp + extra * (1.0 - ramp)).astype(np.float32)


def rope_table(cfg, head_dim):
    """(inverse frequencies (head_dim/2,), cos/sin scale) of cfg's rotary
    embedding: plain theta, or YaRN where `cfg.yarn_factor` is set."""
    if not cfg.yarn_factor:
        return rope_frequencies(head_dim, cfg.rope_theta), 1.0
    inv_freq = yarn_frequencies(
        head_dim, cfg.rope_theta, cfg.yarn_factor,
        cfg.yarn_original_max_positions, cfg.yarn_beta_fast,
        cfg.yarn_beta_slow)
    scale = (yarn_mscale(cfg.yarn_factor, cfg.yarn_mscale)
             / yarn_mscale(cfg.yarn_factor, cfg.yarn_mscale_all_dim))
    return inv_freq, scale


def yarn_softmax_scale(cfg):
    """YaRN's factor on the attention softmax scale: mscale(factor,
    mscale_all_dim) squared, 1 without YaRN."""
    if not (cfg.yarn_factor and cfg.yarn_mscale_all_dim):
        return 1.0
    return yarn_mscale(cfg.yarn_factor, cfg.yarn_mscale_all_dim) ** 2


def apply_rope(x, positions, cfg):
    """x: (..., seq, heads, head_dim); positions: (..., seq). Rotates the
    (first half, second half) pairs of the last axis."""
    half = x.shape[-1] // 2
    inv_freq, scale = rope_table(cfg, x.shape[-1])
    ang = positions.astype(jnp.float32)[..., None] * jnp.asarray(inv_freq)
    cos = jnp.cos(ang)[..., None, :]
    sin = jnp.sin(ang)[..., None, :]
    if scale != 1.0:
        cos, sin = cos * scale, sin * scale
    x1, x2 = x[..., :half], x[..., half:]
    xf1, xf2 = x1.astype(jnp.float32), x2.astype(jnp.float32)
    out = jnp.concatenate(
        [xf1 * cos - xf2 * sin, xf2 * cos + xf1 * sin], axis=-1)
    return out.astype(x.dtype)


# --------------------------------------------------------------------------
# Losses
# --------------------------------------------------------------------------

def softmax_cross_entropy(logits, labels, mask=None):
    """Mean CE over tokens; logits fp32 (..., vocab), labels int (...,).

    The label logit is picked with a where/iota reduction instead of
    take_along_axis — elementwise over the vocab dim, so it stays local when
    logits are vocab-sharded over the "model" mesh axis (GSPMD then emits a
    single small psum for the reduction instead of a gather).
    """
    logits = logits.astype(jnp.float32)
    vocab = logits.shape[-1]
    logz = jax.scipy.special.logsumexp(logits, axis=-1)
    vocab_iota = jax.lax.broadcasted_iota(jnp.int32, logits.shape,
                                          logits.ndim - 1)
    ll = jnp.sum(jnp.where(vocab_iota == labels[..., None], logits, 0.0),
                 axis=-1)
    nll = logz - ll
    if mask is not None:
        mask = mask.astype(jnp.float32)
        return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return jnp.mean(nll)
