"""Plain float32 forward pass of a DeepSeek-V2-architecture scorer, on
one chip's share of an expert-parallel deployment.

Written from the published architecture (DeepSeek-V2, arXiv:2405.04434,
and its Hugging Face `config.json` keys): RMSNorm; multi-head latent
attention without query compression (q_proj; kv_a_proj_with_mqa to a
latent and one shared rotary key, RMSNorm on the latent, kv_b_proj to
per-head keys and values) with causal softmax; YaRN rotary scaling
(`rope_scaling`: blended inverse frequencies, cos/sin times
mscale/mscale_all_dim, the softmax scale times mscale(factor,
mscale_all_dim) squared); a dense SwiGLU for the first
`first_k_dense_replace` layers; then MoE layers: softmax router over every
routed expert, the top `num_experts_per_tok` gates (renormalised only
where `norm_topk_prob`) times `routed_scaling_factor`, each held expert
applied to every token and masked by the routing, plus the shared
experts; the untied head at the last position. Every matmul runs at
HIGHEST precision. It imports nothing of the program.

The one departure: the rotary dims use the (first half, second half)
layout, where DeepSeek's checkpoint interleaves pairs (it permutes them
to halves before rotating). The two are the same model up to a
permutation of the 64 rope columns of q_proj (per head) and of
kv_a_proj_with_mqa: halves column j is published column 2j for j < 32
and 2(j - 32) + 1 above. Random weights make the layouts equivalent.

The chip holds routed experts [first, first + n_routed_experts) of the
router's `deployment.published_n_routed_experts`; what the other experts
would add is left out here as in the program.

`precision` "bfloat16" rounds every matmul operand to bfloat16 (the
served type), "float8" to float8 e4m3 with one scale per tensor (the
control of `score_logp_gap`); the rest stays float32.
"""
from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp

from chipbench import deploy

HIGHEST = jax.lax.Precision.HIGHEST
NORMS = ("ln1", "ln2", "kv_norm", "ln_f")


def router_width(m: dict) -> int:
    return int(m["deployment"]["published_n_routed_experts"])


def canonical_shapes(m: dict) -> dict:
    """The canonical weights by published role, layers stacked: `dense.*`
    for the leading dense layers, `moe.*` for the MoE layers, whose
    routed experts are the held ones."""
    d, v = m["hidden_size"], m["vocab_size"]
    h, r = m["num_attention_heads"], m["kv_lora_rank"]
    dn, dr, dv = (m["qk_nope_head_dim"], m["qk_rope_head_dim"],
                  m["v_head_dim"])
    f, fe = m["intermediate_size"], m["moe_intermediate_size"]
    held, shared = m["n_routed_experts"], m["n_shared_experts"]
    groups = {"dense": m["first_k_dense_replace"],
              "moe": m["num_hidden_layers"] - m["first_k_dense_replace"]}
    out = {"embed": (v, d), "head": (d, v), "ln_f": (d,)}
    for g, n in groups.items():
        out.update({f"{g}.ln1": (n, d), f"{g}.ln2": (n, d),
                    f"{g}.q_proj": (n, d, h * (dn + dr)),
                    f"{g}.kv_a": (n, d, r + dr), f"{g}.kv_norm": (n, r),
                    f"{g}.kv_b": (n, r, h * (dn + dv)),
                    f"{g}.o_proj": (n, h * dv, d)})
    n = groups["moe"]
    out.update({"dense.w_gate": (groups["dense"], d, f),
                "dense.w_up": (groups["dense"], d, f),
                "dense.w_down": (groups["dense"], f, d),
                "moe.router": (n, d, router_width(m)),
                "moe.w_gate": (n, held, d, fe), "moe.w_up": (n, held, d, fe),
                "moe.w_down": (n, held, fe, d),
                "moe.shared_gate": (n, d, shared * fe),
                "moe.shared_up": (n, d, shared * fe),
                "moe.shared_down": (n, shared * fe, d)})
    return out


def make_weights(m: dict, seed: int, dtype=jnp.bfloat16) -> dict:
    """Random canonical weights on the device, one jitted call, keyed as
    `deploy.make_weights` keys them: every matrix N(0, initializer_range)
    rounded to `dtype`, every norm scale 1."""
    shapes = canonical_shapes(m)
    names = sorted(shapes)
    std = float(m["initializer_range"])

    @jax.jit
    def gen(key):
        keys = jax.random.split(key, len(names))
        out = {}
        for k, name in zip(keys, names):
            if name.split(".")[-1] in NORMS:
                out[name] = jnp.ones(shapes[name], jnp.float32)
            else:
                out[name] = (jax.random.normal(k, shapes[name], jnp.float32)
                             * std).astype(dtype)
        return out
    return gen(deploy.stream_key(seed, "weights"))


# -- the layers ---------------------------------------------------------------

def _round(x, precision):
    if precision == "bfloat16":
        return x.astype(jnp.bfloat16).astype(jnp.float32)
    if precision == "float8":
        amax = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
        scale = 448.0 / amax
        return (x * scale).astype(jnp.float8_e4m3fn).astype(
            jnp.float32) / scale
    return x


def _mm(x, w, precision):
    return jnp.matmul(_round(x, precision), _round(w, precision),
                      precision=HIGHEST)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def yarn_get_mscale(scale: float, mscale: float) -> float:
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def yarn_inv_freq(m: dict):
    """YaRN's inverse frequencies of the rotary dims (DeepSeek-V2's
    `DeepseekV2YarnRotaryEmbedding`), float32."""
    rs, dim, base = m["rope_scaling"], m["qk_rope_head_dim"], m["rope_theta"]
    orig = rs["original_max_position_embeddings"]

    def corr(rot):
        return dim * math.log(orig / (rot * 2 * math.pi)) / (
            2 * math.log(base))
    low = max(math.floor(corr(rs["beta_fast"])), 0)
    high = min(math.ceil(corr(rs["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    extra = 1.0 / (base ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    inter = 1.0 / (rs["factor"] * base ** (
        jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    mask = 1.0 - ramp
    return inter * (1.0 - mask) + extra * mask


def _rope(x, m):
    """x: (b, s, heads, dr); YaRN rotary embedding on (first half, second
    half) pairs."""
    rs = m["rope_scaling"]
    s, dr = x.shape[1], x.shape[3]
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * yarn_inv_freq(m)
    mscale = (yarn_get_mscale(rs["factor"], rs["mscale"])
              / yarn_get_mscale(rs["factor"], rs["mscale_all_dim"]))
    cos = (jnp.cos(ang) * mscale)[None, :, None]
    sin = (jnp.sin(ang) * mscale)[None, :, None]
    x1, x2 = x[..., :dr // 2], x[..., dr // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def softmax_scale(m: dict) -> float:
    rs = m["rope_scaling"]
    scale = (m["qk_nope_head_dim"] + m["qk_rope_head_dim"]) ** -0.5
    mall = yarn_get_mscale(rs["factor"], rs["mscale_all_dim"])
    return scale * mall * mall


def attention(x, lw, m, precision):
    """MLA over the whole causal sequence: (b, s, d) -> (b, s, d)."""
    b, s, _ = x.shape
    h, r = m["num_attention_heads"], m["kv_lora_rank"]
    dn, dr, dv = (m["qk_nope_head_dim"], m["qk_rope_head_dim"],
                  m["v_head_dim"])
    q = _mm(x, lw["q_proj"], precision).reshape(b, s, h, dn + dr)
    kv = _mm(x, lw["kv_a"], precision)
    c = _rms(kv[..., :r], lw["kv_norm"], m["rms_norm_eps"])
    k_pe = _rope(kv[..., r:][:, :, None, :], m)
    kvb = _mm(c, lw["kv_b"], precision).reshape(b, s, h, dn + dv)
    q = jnp.concatenate([q[..., :dn], _rope(q[..., dn:], m)], -1)
    k = jnp.concatenate(
        [kvb[..., :dn], jnp.broadcast_to(k_pe, (b, s, h, dr))], -1)
    att = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                     precision=HIGHEST) * softmax_scale(m)
    causal = jnp.tril(jnp.ones((s, s), bool))
    att = jax.nn.softmax(jnp.where(causal, att, -jnp.inf), axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", att, kvb[..., dn:], precision=HIGHEST)
    return _mm(o.reshape(b, s, h * dv), lw["o_proj"], precision)


def swiglu(x, w_gate, w_up, w_down, precision):
    g = jax.nn.silu(_mm(x, w_gate, precision))
    return _mm(g * _mm(x, w_up, precision), w_down, precision)


def routing(x, router, m, precision="float32"):
    """(ids (..., k) of the top experts, gates (..., E): the routed weight
    of every expert, zero off the top k)."""
    probs = jax.nn.softmax(_mm(x, router, precision), axis=-1)
    vals, ids = jax.lax.top_k(probs, m["num_experts_per_tok"])
    if m["norm_topk_prob"]:
        vals = vals / jnp.sum(vals, -1, keepdims=True)
    vals = vals * m["routed_scaling_factor"]
    onehot = jax.nn.one_hot(ids, probs.shape[-1], dtype=jnp.float32)
    return ids, jnp.einsum("...k,...ke->...e", vals, onehot)


def routed_experts(x, lw, m, first, precision="float32"):
    """The held experts' part of the routed sum, each held expert applied
    to every token and weighted by its gate (zero where not chosen)."""
    ids, gates = routing(x, lw["router"], m, precision)
    out = jnp.zeros_like(x)
    for e in range(lw["w_gate"].shape[0]):
        out = out + gates[..., first + e, None] * swiglu(
            x, lw["w_gate"][e], lw["w_up"][e], lw["w_down"][e], precision)
    return out, ids


def moe_layer(x, lw, m, first, precision="float32"):
    """Routed part of the held experts plus the shared experts."""
    out, ids = routed_experts(x, lw, m, first, precision)
    return out + swiglu(x, lw["shared_gate"], lw["shared_up"],
                        lw["shared_down"], precision), ids


# -- the model ----------------------------------------------------------------

def _layers(w, group):
    pre = group + "."
    return {k[len(pre):]: v for k, v in w.items() if k.startswith(pre)}


@functools.partial(jax.jit, static_argnames=("m_json", "precision"))
def _forward(w, tokens, m_json, precision):
    m = json.loads(m_json)
    eps = m["rms_norm_eps"]
    first = int(m["deployment"]["first_held_expert"])
    f32 = functools.partial(jax.tree.map, lambda a: a.astype(jnp.float32))
    x = w["embed"][tokens].astype(jnp.float32)

    def dense(x, lw):
        lw = f32(lw)
        x = x + attention(_rms(x, lw["ln1"], eps), lw, m, precision)
        y = _rms(x, lw["ln2"], eps)
        return x + swiglu(y, lw["w_gate"], lw["w_up"], lw["w_down"],
                          precision), None

    def sparse(x, lw):
        lw = f32(lw)
        x = x + attention(_rms(x, lw["ln1"], eps), lw, m, precision)
        out, ids = moe_layer(_rms(x, lw["ln2"], eps), lw, m, first,
                             precision)
        return x + out, ids

    x, _ = jax.lax.scan(dense, x, _layers(w, "dense"))
    x, ids = jax.lax.scan(sparse, x, _layers(w, "moe"))
    last = _rms(x[:, -1], w["ln_f"], eps)
    return _mm(last, w["head"].astype(jnp.float32), precision), ids


def last_logits(w: dict, tokens, m: dict, precision: str = "float32"):
    """(logits at the last position (b, vocab); the top-k expert ids of
    every MoE layer and token (layers, b, s, k))."""
    keys = ("hidden_size", "num_attention_heads", "kv_lora_rank",
            "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
            "rms_norm_eps", "rope_theta", "rope_scaling",
            "num_experts_per_tok", "norm_topk_prob", "routed_scaling_factor",
            "deployment")
    m_json = json.dumps({k: m[k] for k in keys}, sort_keys=True)
    with jax.default_matmul_precision("highest"):
        return _forward(w, jnp.asarray(tokens, jnp.int32), m_json, precision)


def forward(w: dict, tokens, m: dict, target: int,
            precision: str = "float32"):
    """(log p(target | record) at the last position, one per record; the
    top-k expert ids of every MoE layer and token)."""
    logits, ids = last_logits(w, tokens, m, precision)
    return jax.nn.log_softmax(logits, axis=-1)[:, target], ids
