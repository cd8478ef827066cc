"""Plain float32 forward pass of a Llama-architecture scorer.

Written from the published architecture (RMSNorm, rotary embeddings
with the two-halves layout, grouped-query causal softmax attention,
SwiGLU MLP, tied embedding head), in straightforward `jax.numpy` with
every matmul at HIGHEST precision. It reads the canonical weights of
`deploy.make_weights` and imports nothing of the program.

`quantize` turns it into the control: every matmul operand rounded to
float8 (e4m3, one scale per tensor, as an fp8 serving path would), the
rest in float32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def _fp8(x):
    """Round to float8 e4m3 with one scale per tensor, back in float32."""
    amax = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    scale = 448.0 / amax
    return (x * scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) / scale


def _mm(x, w, quantize):
    if quantize:
        x, w = _fp8(x), _fp8(w)
    return jnp.matmul(x, w, precision=HIGHEST)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, theta):
    """x: (b, s, heads, hd); rotary embedding on (first half, second half)
    pairs."""
    s, hd = x.shape[1], x.shape[3]
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs   # (s, hd/2)
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.partial(jax.jit, static_argnames=("m_items", "target",
                                             "quantize"))
def _forward(w, tokens, m_items, target, quantize):
    m = dict(m_items)
    d, h = m["hidden_size"], m["num_attention_heads"]
    kv = m["num_key_value_heads"]
    hd = m.get("head_dim", d // h)
    eps, theta = m["rms_norm_eps"], m["rope_theta"]
    w = jax.tree.map(lambda a: a.astype(jnp.float32), w)
    b, s = tokens.shape
    x = w["embed"][tokens]
    causal = jnp.tril(jnp.ones((s, s), bool))

    def layer(x, lw):
        y = _rms(x, lw["ln1"], eps)
        q = _rope(_mm(y, lw["wq"], quantize).reshape(b, s, h, hd), theta)
        k = _rope(_mm(y, lw["wk"], quantize).reshape(b, s, kv, hd), theta)
        v = _mm(y, lw["wv"], quantize).reshape(b, s, kv, hd)
        k = jnp.repeat(k, h // kv, axis=2)
        v = jnp.repeat(v, h // kv, axis=2)
        att = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                         precision=HIGHEST) / jnp.sqrt(jnp.float32(hd))
        att = jax.nn.softmax(jnp.where(causal, att, -jnp.inf), axis=-1)
        o = jnp.einsum("bhqk,bkhd->bqhd", att, v, precision=HIGHEST)
        x = x + _mm(o.reshape(b, s, h * hd), lw["wo"], quantize)
        y = _rms(x, lw["ln2"], eps)
        g = jax.nn.silu(_mm(y, lw["w_gate"], quantize))
        x = x + _mm(g * _mm(y, lw["w_up"], quantize), lw["w_down"],
                    quantize)
        return x, None

    stacked = {k: w[k] for k in ("ln1", "ln2", "wq", "wk", "wv", "wo",
                                 "w_gate", "w_up", "w_down")}
    x, _ = jax.lax.scan(layer, x, stacked)
    last = _rms(x[:, -1], w["ln_f"], eps)
    logits = _mm(last, w["embed"].T, quantize)
    return jax.nn.log_softmax(logits, axis=-1)[:, target]


def target_logprob(w: dict, tokens, m: dict, target: int,
                   quantize: bool = False):
    """log p(target | record) at the last position, one per record."""
    keys = ("hidden_size", "num_attention_heads", "num_key_value_heads",
            "rms_norm_eps", "rope_theta") + (("head_dim",) if "head_dim"
                                              in m else ())
    items = tuple((k, m[k]) for k in keys)
    return _forward(w, jnp.asarray(tokens, jnp.int32), items, int(target),
                    bool(quantize))
