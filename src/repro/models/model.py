"""Top-level model API: init / train logits / prefill scoring / decode.

    params = init(key, cfg)
    logits, aux = apply_train(params, cfg, tokens)          (B,S,V) or (B,S,K,V)
    logits, aux = last_logits(params, cfg, tokens)          (B,V) or (B,K,V)
    scores      = proxy_scores(params, cfg, tokens, target) (B,) in [0,1]
    logits, caches = apply_decode(params, cfg, tokens, caches, pos)
    caches      = init_caches(cfg, batch, seq_len)

The proxy-score head is how the SUPG plane consumes a model: the score of a
record is the model's probability mass on a designated predicate token at
the last position — calibrated-ish, in [0,1], exactly the A(x) the paper
assumes (Sec 4.1: "executes the proxy model over the complete set of
records").
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models import attention, layers, mamba, rwkv, transformer


# --------------------------------------------------------------------------
# Init
# --------------------------------------------------------------------------

def init(key, cfg):
    k_emb, k_body, k_head = jax.random.split(key, 3)
    dt = layers.dtype_of(cfg)
    if cfg.num_codebooks > 1:
        emb = {"table": jax.vmap(
            lambda k: layers.init_embedding(k, cfg.vocab_size, cfg.d_model,
                                            dt)["table"])(
            jax.random.split(k_emb, cfg.num_codebooks))}
    else:
        emb = layers.init_embedding(k_emb, cfg.vocab_size, cfg.d_model, dt)
    params = {
        "embed": emb,
        "body": transformer.init_body(k_body, cfg),
        "ln_f": layers.init_rmsnorm(cfg.d_model),
    }
    if not cfg.tie_embeddings:
        if cfg.num_codebooks > 1:
            params["head"] = {"w": jax.vmap(
                lambda k: layers.dense_init(k, cfg.d_model, cfg.vocab_size,
                                            dt))(
                jax.random.split(k_head, cfg.num_codebooks))}
        else:
            params["head"] = layers.init_lm_head(
                k_head, cfg.d_model, cfg.vocab_size, dt)
    return params


def _embed(params, cfg, tokens):
    if cfg.num_codebooks > 1:
        # tokens: (B, S, K) — sum the K codebook embeddings (MusicGen).
        embs = jnp.einsum("bskd->bsd", jax.vmap(
            lambda t, tab: jnp.take(tab, t, axis=0),
            in_axes=(2, 0), out_axes=2)(tokens, params["embed"]["table"]))
        return embs
    return layers.embed(params["embed"], tokens)


def _head(params, cfg, x):
    if cfg.tie_embeddings:
        return layers.unembed(params["embed"], x)
    if cfg.num_codebooks > 1:
        return jnp.einsum("bsd,kdv->bskv", x, params["head"]["w"],
                          preferred_element_type=jnp.float32)
    return layers.lm_head(params["head"], x)


# --------------------------------------------------------------------------
# Forward passes
# --------------------------------------------------------------------------

def _constrain_vocab(cfg, logits):
    """Vocab-shard the logits over the 'model' axis (fp32 logits at 32k+
    vocab dominate train-step HBM otherwise). UNCONSTRAINED elsewhere so
    GSPMD keeps the batch layout it propagated."""
    if not cfg.shard_activations:
        return logits
    from jax.sharding import PartitionSpec as P
    u = P.UNCONSTRAINED
    spec = P(*([u] * (logits.ndim - 1) + ["model"]))
    return jax.lax.with_sharding_constraint(logits, spec)


def _body(params, cfg, tokens, q_chunk, kv_chunk):
    """Embedding and layers: (hidden states before the final norm, aux)."""
    b = tokens.shape[0]
    s = tokens.shape[1]
    if cfg.unroll_layers:
        # cost-probe mode: no attention chunk scans either — XLA's cost
        # model counts while bodies once, so probes must be loop-free.
        q_chunk = kv_chunk = s
    x = _embed(params, cfg, tokens)
    positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
    return transformer.body_prefill(params["body"], cfg, x, positions,
                                    q_chunk=q_chunk, kv_chunk=kv_chunk)


def apply_train(params, cfg, tokens, q_chunk=1024, kv_chunk=1024):
    """Training/prefill logits over the full sequence, and the body's aux
    (`transformer.body_prefill`)."""
    x, aux = _body(params, cfg, tokens, q_chunk, kv_chunk)
    x = layers.rms_norm(params["ln_f"], x, cfg.norm_eps)
    return _constrain_vocab(cfg, _head(params, cfg, x)), aux


def last_logits(params, cfg, tokens):
    """Logits at the last position only, and the body's aux: the head is
    applied to one position a record."""
    x, aux = _body(params, cfg, tokens, 1024, 1024)
    x = layers.rms_norm(params["ln_f"], x[:, -1:], cfg.norm_eps)
    return _head(params, cfg, x)[:, 0], aux


def loss_fn(params, cfg, tokens, labels, mask=None):
    logits, aux = apply_train(params, cfg, tokens)
    if cfg.num_codebooks > 1:
        ce = layers.softmax_cross_entropy(
            logits.reshape(-1, cfg.vocab_size), labels.reshape(-1))
    else:
        ce = layers.softmax_cross_entropy(logits, labels, mask)
    return ce + aux["loss"], (ce, aux["loss"])


def proxy_scores(params, cfg, tokens, target_token=1):
    """A(x) in [0,1]: probability of the predicate token at the last step."""
    last, _ = last_logits(params, cfg, tokens)
    if cfg.num_codebooks > 1:
        last = last.mean(axis=1)
    p = jax.nn.softmax(last.astype(jnp.float32), axis=-1)
    return p[..., target_token]


def apply_decode(params, cfg, tokens, caches, pos):
    """tokens: (B,1) or (B,1,K); pos: (B,). Returns (logits, new_caches)."""
    x = _embed(params, cfg, tokens)
    x, new_caches = transformer.body_decode(params["body"], cfg, x,
                                            caches, pos)
    x = layers.rms_norm(params["ln_f"], x, cfg.norm_eps)
    return _head(params, cfg, x), new_caches


# --------------------------------------------------------------------------
# Cache construction
# --------------------------------------------------------------------------

def _attn_cache(cfg, batch, seq_len, dtype):
    spec = (attention.mla_cache_spec if cfg.use_mla
            else attention.gqa_cache_spec)(cfg, batch, seq_len, dtype)
    return {k: jnp.zeros(shape, dt) for k, (shape, dt) in spec.items()}


def _stack(n, tree):
    return jax.tree.map(lambda x: jnp.broadcast_to(x[None], (n,) + x.shape),
                        tree)


def init_caches(cfg, batch, seq_len, dtype=jnp.bfloat16):
    """Zeroed decode caches matching body_decode's expected structure."""
    if cfg.block == "rwkv":
        return {"blocks": _stack(cfg.num_layers,
                                 rwkv.init_rwkv_state(cfg, batch, dtype))}
    if cfg.block == "mamba":
        n_super = cfg.num_layers // cfg.shared_attn_every if \
            cfg.shared_attn_every else 0
        per = cfg.shared_attn_every
        tail = cfg.num_layers - n_super * per
        out = {
            "mamba_super": _stack(max(n_super, 1), _stack(
                per or 1, mamba.init_mamba_state(cfg, batch, dtype))),
            "shared_attn": _stack(max(n_super, 1),
                                  _attn_cache(cfg, batch, seq_len, dtype)),
        }
        if tail:
            out["mamba_tail"] = _stack(
                tail, mamba.init_mamba_state(cfg, batch, dtype))
        return out
    if cfg.moe and cfg.moe_layer_step > 1:
        n_pairs = cfg.num_layers // cfg.moe_layer_step
        return {"dense": _stack(n_pairs,
                                _attn_cache(cfg, batch, seq_len, dtype)),
                "moe": _stack(n_pairs,
                              _attn_cache(cfg, batch, seq_len, dtype))}
    if cfg.moe:
        n_moe = cfg.num_layers - cfg.first_k_dense
        return {
            "dense_prefix": _stack(max(cfg.first_k_dense, 1),
                                   _attn_cache(cfg, batch, seq_len, dtype)),
            "moe_blocks": _stack(n_moe,
                                 _attn_cache(cfg, batch, seq_len, dtype)),
        }
    return {"blocks": _stack(cfg.num_layers,
                             _attn_cache(cfg, batch, seq_len, dtype))}


# --------------------------------------------------------------------------
# Analytic parameter / FLOP counts (roofline denominators)
# --------------------------------------------------------------------------

def count_params_analytic(cfg, active_only=False):
    d, L, V = cfg.d_model, cfg.num_layers, cfg.vocab_size
    total = V * d * cfg.num_codebooks          # embedding
    if not cfg.tie_embeddings:
        total += d * V * cfg.num_codebooks     # head

    if cfg.block == "rwkv":
        per = 5 * d * d + d * cfg.d_ff * 2 + d * d   # tm + cm projections
        per += 5 * cfg.rwkv_lora_dim * d * 2 + 2 * cfg.rwkv_lora_dim * d * 2
        return total + L * per

    if cfg.block == "mamba":
        d_in = cfg.ssm_expand * d
        n = cfg.ssm_state_dim
        h = d_in // cfg.ssm_head_dim
        per = d * (2 * d_in + 2 * n + h) + d_in * d
        n_super = L // cfg.shared_attn_every if cfg.shared_attn_every else 0
        shared = 0
        if cfg.shared_attn_every:
            hd = cfg.head_dim
            shared = d * hd * (cfg.num_heads + 2 * cfg.num_kv_heads) \
                + cfg.num_heads * hd * d + 3 * d * cfg.d_ff
        return total + L * per + shared

    # attention params
    if cfg.use_mla:
        attn = d * (cfg.q_lora_rank or 0)
        q_in = cfg.q_lora_rank if cfg.q_lora_rank else d
        attn += q_in * cfg.num_heads * (cfg.qk_nope_head_dim
                                        + cfg.qk_rope_head_dim)
        attn += d * (cfg.kv_lora_rank + cfg.qk_rope_head_dim)
        attn += cfg.kv_lora_rank * cfg.num_heads * (
            cfg.qk_nope_head_dim + cfg.v_head_dim)
        attn += cfg.num_heads * cfg.v_head_dim * d
    else:
        hd = cfg.head_dim
        attn = d * hd * (cfg.num_heads + 2 * cfg.num_kv_heads) \
            + cfg.num_heads * hd * d

    mlp_dense = 3 * d * (cfg.dense_d_ff or cfg.d_ff)

    if cfg.moe:
        expert = 3 * d * cfg.moe_d_ff
        shared = 3 * d * cfg.moe_d_ff * cfg.num_shared_experts
        router = d * cfg.num_experts
        if cfg.moe_layer_step > 1:
            n_moe = L // cfg.moe_layer_step
            n_dense = L - n_moe
        else:
            n_moe = L - cfg.first_k_dense
            n_dense = cfg.first_k_dense
        e_count = (cfg.num_experts_per_tok if active_only
                   else cfg.num_experts)
        return total + L * attn + n_dense * mlp_dense \
            + n_moe * (expert * e_count + shared + router)

    return total + L * (attn + mlp_dense)


def train_flops_analytic(cfg, batch, seq):
    """6·N_active·D (+ attention quadratic term) — the §Roofline MODEL_FLOPS."""
    n_active = count_params_analytic(cfg, active_only=True)
    flops = 6.0 * n_active * batch * seq
    if cfg.num_heads and cfg.block == "attn":
        hd = cfg.head_dim if not cfg.use_mla else (
            cfg.qk_nope_head_dim + cfg.qk_rope_head_dim + cfg.v_head_dim)
        # causal: 2 matmuls * S^2/2 * heads * hd, *3 for fwd+bwd, per layer
        flops += 3.0 * 2.0 * batch * seq * seq * cfg.num_heads * hd \
            * cfg.num_layers / 2.0
    return flops
