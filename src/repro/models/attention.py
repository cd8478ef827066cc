"""Attention: GQA/MHA (+QKV-bias, qk-norm) and MLA (DeepSeek-V2), with
chunked-online-softmax prefill and KV-cache decode.

Prefill uses a two-level blocked online-softmax scan (`chunked_causal_attention`)
— mathematically exact, bounded intermediates (never materializes S x S), and
the jnp analogue of the Pallas flash_attention kernel (kernels/flash_attention
is the TPU hot path; this path is what the dry-run lowers).

Decode attends a single new token against a (B, S, KV, hd) cache. MLA decode
uses the *absorbed* formulation: scores and outputs live in the compressed
latent space (kv_lora + rope dims per token — MQA-grade cache traffic), which
is the technique's entire point.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models import layers
from repro.models.layers import dense_init, matmul, matmul_rowparallel

NEG_INF = -1e30


# --------------------------------------------------------------------------
# Blocked causal attention (exact, online softmax)
# --------------------------------------------------------------------------

def chunked_causal_attention(q, k, v, *, q_chunk=1024, kv_chunk=1024,
                             scale=None):
    """q: (B,S,H,dh), k/v: (B,S,KV,dh) -> (B,S,H,dh). Causal, GQA-aware.

    Two-level lax.scan with online softmax: outer over query chunks, inner
    over kv chunks (only chunks at-or-before the query chunk contribute).
    Exact — matches plain softmax attention to fp32 tolerance. `scale`
    multiplies the scores (default 1/sqrt(dh)).
    """
    b, s, h, dh = q.shape
    kv = k.shape[2]
    dv = v.shape[3]                      # may differ from dh (MLA)
    g = h // kv
    q_chunk = min(q_chunk, s)
    kv_chunk = min(kv_chunk, s)
    nq, nk = s // q_chunk, s // kv_chunk
    assert s % q_chunk == 0 and s % kv_chunk == 0, "seq not chunk-divisible"

    qc = q.reshape(b, nq, q_chunk, kv, g, dh)
    kc = k.reshape(b, nk, kv_chunk, kv, dh)
    vc = v.reshape(b, nk, kv_chunk, kv, dv)
    if scale is None:
        scale = 1.0 / jnp.sqrt(jnp.float32(dh))

    def outer(_, qi):
        qblk, qidx = qi                      # (b, qc, kv, g, dh), scalar
        q_pos = qidx * q_chunk + jnp.arange(q_chunk)

        @jax.checkpoint
        def inner(carry, ki):
            acc, m_run, l_run = carry
            kblk, vblk, kidx = ki
            k_pos = kidx * kv_chunk + jnp.arange(kv_chunk)
            scores = jnp.einsum("bqkgd,bpkd->bkgqp", qblk, kblk,
                                preferred_element_type=jnp.float32) * scale
            mask = q_pos[:, None] >= k_pos[None, :]
            scores = jnp.where(mask[None, None, None], scores, NEG_INF)
            m_new = jnp.maximum(m_run, jnp.max(scores, axis=-1))
            p = jnp.exp(scores - m_new[..., None])
            corr = jnp.exp(m_run - m_new)
            l_new = l_run * corr + jnp.sum(p, axis=-1)
            pv = jnp.einsum("bkgqp,bpkd->bkgqd", p,
                            vblk.astype(jnp.float32),
                            preferred_element_type=jnp.float32)
            acc = acc * corr[..., None] + pv
            return (acc, m_new, l_new), None

        # Skip fully-masked kv chunks: static slice bound via dynamic trip
        # count is not scannable, so mask handles causality; XLA still
        # executes all chunks — the Pallas kernel skips them for real.
        acc0 = jnp.zeros((b, kv, g, q_chunk, dv), jnp.float32)
        m0 = jnp.full((b, kv, g, q_chunk), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, kv, g, q_chunk), jnp.float32)
        (acc, _, l), _ = jax.lax.scan(
            inner, (acc0, m0, l0),
            (kc.transpose(1, 0, 2, 3, 4), vc.transpose(1, 0, 2, 3, 4),
             jnp.arange(nk)))
        out = acc / jnp.maximum(l[..., None], 1e-30)
        return None, out.astype(q.dtype)

    _, blocks = jax.lax.scan(
        outer, None, (qc.transpose(1, 0, 2, 3, 4, 5), jnp.arange(nq)))
    # blocks: (nq, b, kv, g, q_chunk, dv) -> (b, s, h, dv)
    out = blocks.transpose(1, 0, 4, 2, 3, 5).reshape(b, s, h, dv)
    return out


def context_parallel_attention(q, k, v, *, m_size, q_chunk=None,
                               kv_chunk=1024, scale=None):
    """Causal attention with the query-chunk axis BATCHED (not scanned) and
    sharded over the "model" mesh axis — context parallelism.

    Motivation (§Perf iteration 2): archs whose head counts don't divide
    TP-16 (smollm 15H, qwen 20H, musicgen 24H) get their attention fully
    replicated across the model axis by GSPMD — 16x wasted FLOPs at 32k
    prefill. Sharding the *sequence* instead is head-count-agnostic: each
    model shard owns nq/16 query chunks and attends them against the full
    K/V (which GQA keeps small). The kv-chunk loop stays an online-softmax
    scan, so peak memory per device matches the scanned form once the nq
    axis is sharded.
    """
    b, s, h, dh = q.shape
    kv = k.shape[2]
    dv = v.shape[3]
    g = h // kv
    nq = m_size * max(1, s // (1024 * m_size))
    if q_chunk is None:
        q_chunk = s // nq
    nq = s // q_chunk
    kv_chunk = min(kv_chunk, s)
    nk = s // kv_chunk
    assert s % q_chunk == 0 and s % kv_chunk == 0

    from jax.sharding import PartitionSpec as P
    u = P.UNCONSTRAINED
    qc = q.reshape(b, nq, q_chunk, kv, g, dh)
    if nq % m_size == 0:
        qc = jax.lax.with_sharding_constraint(
            qc, P(u, "model", u, u, u, u))
    kc = k.reshape(b, nk, kv_chunk, kv, dh)
    vc = v.reshape(b, nk, kv_chunk, kv, dv)
    if scale is None:
        scale = 1.0 / jnp.sqrt(jnp.float32(dh))
    q_pos = (jnp.arange(nq)[:, None] * q_chunk
             + jnp.arange(q_chunk)[None, :])          # (nq, qc)

    @jax.checkpoint
    def inner(carry, ki):
        acc, m_run, l_run = carry
        kblk, vblk, kidx = ki
        k_pos = kidx * kv_chunk + jnp.arange(kv_chunk)
        scores = jnp.einsum("bnckgd,bpkd->bnkgcp", qc, kblk,
                            preferred_element_type=jnp.float32) * scale
        mask = q_pos[:, :, None] >= k_pos[None, None, :]   # (nq, qc, kvc)
        scores = jnp.where(mask[None, :, None, None], scores, NEG_INF)
        m_new = jnp.maximum(m_run, jnp.max(scores, axis=-1))
        p = jnp.exp(scores - m_new[..., None])
        corr = jnp.exp(m_run - m_new)
        l_new = l_run * corr + jnp.sum(p, axis=-1)
        pv = jnp.einsum("bnkgcp,bpkd->bnkgcd", p, vblk.astype(jnp.float32),
                        preferred_element_type=jnp.float32)
        acc = acc * corr[..., None] + pv
        return (acc, m_new, l_new), None

    acc0 = jnp.zeros((b, nq, kv, g, q_chunk, dv), jnp.float32)
    m0 = jnp.full((b, nq, kv, g, q_chunk), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, nq, kv, g, q_chunk), jnp.float32)
    (acc, _, l), _ = jax.lax.scan(
        inner, (acc0, m0, l0),
        (kc.transpose(1, 0, 2, 3, 4), vc.transpose(1, 0, 2, 3, 4),
         jnp.arange(nk)))
    out = acc / jnp.maximum(l[..., None], 1e-30)
    # (b, nq, kv, g, qc, dv) -> (b, s, h, dv)
    return out.transpose(0, 1, 4, 2, 3, 5).reshape(b, s, h, dv).astype(
        q.dtype)


def _attention_dispatch(cfg, q, k, v, q_chunk, kv_chunk, scale=None):
    """Pick scanned (memory-lean default) vs context-parallel (production
    mesh) blocked attention."""
    if cfg.shard_activations:
        from repro.models import meshctx
        mesh = meshctx.current_mesh()
        if mesh is not None and "model" in mesh.axis_names:
            m_size = dict(zip(mesh.axis_names, mesh.devices.shape))["model"]
            s = q.shape[1]
            if m_size > 1 and s % (m_size * 128) == 0:
                # probe mode: loop-free (kv unchunked) so costs are counted
                kvc = s if cfg.unroll_layers else kv_chunk
                return context_parallel_attention(q, k, v, m_size=m_size,
                                                  kv_chunk=kvc, scale=scale)
    return chunked_causal_attention(q, k, v, q_chunk=q_chunk,
                                    kv_chunk=kv_chunk, scale=scale)


def decode_attention(q, cache_k, cache_v, pos):
    """q: (B,1,H,dh); cache: (B,S,KV,dh); pos: (B,) current index.

    Attends over cache positions <= pos. Returns (B,1,H,dh).
    """
    b, _, h, dh = q.shape
    s, kv = cache_k.shape[1], cache_k.shape[2]
    g = h // kv
    qg = q.reshape(b, kv, g, dh)
    scale = 1.0 / jnp.sqrt(jnp.float32(dh))
    scores = jnp.einsum("bkgd,bskd->bkgs", qg, cache_k,
                        preferred_element_type=jnp.float32) * scale
    valid = jnp.arange(s)[None] <= pos[:, None]          # (B,S)
    scores = jnp.where(valid[:, None, None], scores, NEG_INF)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgs,bskd->bkgd", p, cache_v.astype(jnp.float32),
                     preferred_element_type=jnp.float32)
    return out.reshape(b, 1, h, dh).astype(q.dtype)


# --------------------------------------------------------------------------
# GQA attention layer
# --------------------------------------------------------------------------

def init_gqa(key, cfg):
    d, h, kvh, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 4)
    dt = layers.dtype_of(cfg)
    p = {
        "wq": dense_init(ks[0], d, h * hd, dt),
        "wk": dense_init(ks[1], d, kvh * hd, dt),
        "wv": dense_init(ks[2], d, kvh * hd, dt),
        "wo": dense_init(ks[3], h * hd, d, dt),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((h * hd,), dt)
        p["bk"] = jnp.zeros((kvh * hd,), dt)
        p["bv"] = jnp.zeros((kvh * hd,), dt)
    if cfg.qk_norm:
        p["q_norm"] = layers.init_rmsnorm(hd)
        p["k_norm"] = layers.init_rmsnorm(hd)
    return p


def _gqa_qkv(p, cfg, x, positions):
    b, s, _ = x.shape
    h, kvh, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q = matmul(x, p["wq"])
    k = matmul(x, p["wk"])
    v = matmul(x, p["wv"])
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = q.reshape(b, s, h, hd)
    k = k.reshape(b, s, kvh, hd)
    v = v.reshape(b, s, kvh, hd)
    if cfg.qk_norm:
        q = layers.rms_norm(p["q_norm"], q, cfg.norm_eps)
        k = layers.rms_norm(p["k_norm"], k, cfg.norm_eps)
    q = layers.apply_rope(q, positions, cfg)
    k = layers.apply_rope(k, positions, cfg)
    return q, k, v


def gqa_prefill(p, cfg, x, positions, q_chunk=1024, kv_chunk=1024):
    b, s, _ = x.shape
    q, k, v = _gqa_qkv(p, cfg, x, positions)
    o = _attention_dispatch(cfg, q, k, v, q_chunk, kv_chunk)
    return matmul_rowparallel(o.reshape(b, s, -1), p["wo"], cfg)


def gqa_decode(p, cfg, x, cache, pos):
    """x: (B,1,d); cache: {'k','v'}: (B,S,KV,hd); pos: (B,)."""
    b = x.shape[0]
    q, k_new, v_new = _gqa_qkv(p, cfg, x, pos[:, None])
    upd = jax.vmap(lambda c, n, i: jax.lax.dynamic_update_slice(
        c, n, (i, 0, 0)))
    cache_k = upd(cache["k"], k_new, pos)
    cache_v = upd(cache["v"], v_new, pos)
    o = decode_attention(q, cache_k, cache_v, pos)
    y = matmul(o.reshape(b, 1, -1), p["wo"])
    return y, {"k": cache_k, "v": cache_v}


def gqa_cache_spec(cfg, batch, seq_len, dtype):
    kvh, hd = cfg.num_kv_heads, cfg.head_dim
    shape = (batch, seq_len, kvh, hd)
    return {"k": (shape, dtype), "v": (shape, dtype)}


# --------------------------------------------------------------------------
# MLA — Multi-head Latent Attention (DeepSeek-V2)
# --------------------------------------------------------------------------

def init_mla(key, cfg):
    d = cfg.d_model
    h = cfg.num_heads
    r_q, r_kv = cfg.q_lora_rank, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    dt = layers.dtype_of(cfg)
    ks = jax.random.split(key, 7)
    p = {
        "w_dkv": dense_init(ks[2], d, r_kv + dr, dt),   # latent + shared rope
        "kv_norm": layers.init_rmsnorm(r_kv),
        "w_uk": dense_init(ks[3], r_kv, h * dn, dt),
        "w_uv": dense_init(ks[4], r_kv, h * dv, dt),
        "wo": dense_init(ks[5], h * dv, d, dt),
    }
    if r_q:
        p["w_dq"] = dense_init(ks[0], d, r_q, dt)
        p["q_norm"] = layers.init_rmsnorm(r_q)
        p["w_uq"] = dense_init(ks[1], r_q, h * (dn + dr), dt)
    else:
        p["wq"] = dense_init(ks[0], d, h * (dn + dr), dt)
    return p


def _mla_q(p, cfg, x, positions):
    b, s, _ = x.shape
    h = cfg.num_heads
    dn, dr = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    if cfg.q_lora_rank:
        q = matmul(layers.rms_norm(p["q_norm"], matmul(x, p["w_dq"]),
                                   cfg.norm_eps), p["w_uq"])
    else:
        q = matmul(x, p["wq"])
    q = q.reshape(b, s, h, dn + dr)
    q_nope, q_rope = q[..., :dn], q[..., dn:]
    q_rope = layers.apply_rope(q_rope, positions, cfg)
    return q_nope, q_rope


def _mla_latent(p, cfg, x, positions):
    dkv = matmul(x, p["w_dkv"])
    c = layers.rms_norm(p["kv_norm"], dkv[..., :cfg.kv_lora_rank],
                        cfg.norm_eps)
    k_rope = dkv[..., cfg.kv_lora_rank:][..., None, :]   # shared single head
    k_rope = layers.apply_rope(k_rope, positions, cfg)[..., 0, :]
    return c, k_rope


def _mla_softmax_scale(cfg):
    """1/sqrt(nope + rope head dims), times YaRN's mscale squared."""
    return (layers.yarn_softmax_scale(cfg)
            / float(cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** 0.5)


def mla_prefill(p, cfg, x, positions, q_chunk=1024, kv_chunk=1024):
    """Materialized (training-style) MLA attention."""
    with jax.named_scope("supg.mla"):
        b, s, _ = x.shape
        h = cfg.num_heads
        dn, dr = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
        dv = cfg.v_head_dim
        q_nope, q_rope = _mla_q(p, cfg, x, positions)
        c, k_rope = _mla_latent(p, cfg, x, positions)
        k_nope = matmul(c, p["w_uk"]).reshape(b, s, h, dn)
        v = matmul(c, p["w_uv"]).reshape(b, s, h, dv)
        q = jnp.concatenate([q_nope, q_rope], axis=-1)
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_rope[:, :, None, :], (b, s, h, dr))],
            axis=-1)
        o = _attention_dispatch(cfg, q, k, v, q_chunk, kv_chunk,
                                scale=_mla_softmax_scale(cfg))
        return matmul_rowparallel(o.reshape(b, s, -1), p["wo"], cfg)


def mla_decode(p, cfg, x, cache, pos):
    """Absorbed-latent decode: cache = {'c': (B,S,r_kv), 'k_rope': (B,S,dr)}.

    Per-token score: q_nope W_uk . c_s  +  q_rope . k_rope_s, computed
    without materializing per-head K/V — the cache line per token is
    (r_kv + dr) = 576 floats regardless of the 128 heads.
    """
    b = x.shape[0]
    h = cfg.num_heads
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    r_kv = cfg.kv_lora_rank
    q_nope, q_rope = _mla_q(p, cfg, x, pos[:, None])     # (B,1,H,*)
    c_new, kr_new = _mla_latent(p, cfg, x, pos[:, None])
    upd2 = jax.vmap(lambda cch, n, i: jax.lax.dynamic_update_slice(
        cch, n, (i, 0)))
    cache_c = upd2(cache["c"], c_new, pos)
    cache_kr = upd2(cache["k_rope"], kr_new, pos)

    w_uk = p["w_uk"].reshape(r_kv, h, dn)
    # Absorb W_uk into the query: (B,H,r_kv)
    q_lat = jnp.einsum("bhd,rhd->bhr", q_nope[:, 0], w_uk,
                       preferred_element_type=jnp.float32)
    s_lat = jnp.einsum("bhr,bsr->bhs", q_lat,
                       cache_c.astype(jnp.float32),
                       preferred_element_type=jnp.float32)
    s_rope = jnp.einsum("bhd,bsd->bhs", q_rope[:, 0].astype(jnp.float32),
                        cache_kr.astype(jnp.float32),
                        preferred_element_type=jnp.float32)
    scores = (s_lat + s_rope) * _mla_softmax_scale(cfg)
    valid = jnp.arange(cache_c.shape[1])[None] <= pos[:, None]
    scores = jnp.where(valid[:, None], scores, NEG_INF)
    pattn = jax.nn.softmax(scores, axis=-1)
    o_lat = jnp.einsum("bhs,bsr->bhr", pattn, cache_c.astype(jnp.float32),
                       preferred_element_type=jnp.float32)
    w_uv = p["w_uv"].reshape(r_kv, h, dv)
    o = jnp.einsum("bhr,rhd->bhd", o_lat, w_uv.astype(jnp.float32),
                   preferred_element_type=jnp.float32)
    y = matmul(o.reshape(b, 1, -1).astype(x.dtype), p["wo"])
    return y, {"c": cache_c, "k_rope": cache_kr}


def mla_cache_spec(cfg, batch, seq_len, dtype):
    return {"c": ((batch, seq_len, cfg.kv_lora_rank), dtype),
            "k_rope": ((batch, seq_len, cfg.qk_rope_head_dim), dtype)}
