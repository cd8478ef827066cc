"""Per-arch smoke tests (reduced configs) + prefill/decode consistency."""
import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, get_config, get_smoke_config, SHAPES
from repro.configs.base import shape_applicable
from repro.models import layers, model

KEY = jax.random.PRNGKey(0)
B, S = 2, 32


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_arch_smoke_forward_and_loss(arch):
    cfg = get_smoke_config(arch)
    params = model.init(KEY, cfg)
    tok_shape = (B, S, cfg.num_codebooks) if cfg.num_codebooks > 1 else (B, S)
    tokens = jax.random.randint(KEY, tok_shape, 0, cfg.vocab_size)
    logits, aux = model.apply_train(params, cfg, tokens)
    expect = (B, S, cfg.num_codebooks, cfg.vocab_size) \
        if cfg.num_codebooks > 1 else (B, S, cfg.vocab_size)
    assert logits.shape == expect
    assert not bool(jnp.any(jnp.isnan(logits)))
    loss, (ce, _) = model.loss_fn(params, cfg, tokens, tokens)
    assert np.isfinite(float(loss))


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_arch_smoke_decode_step(arch):
    cfg = get_smoke_config(arch)
    params = model.init(KEY, cfg)
    caches = model.init_caches(cfg, B, S, jnp.float32)
    tok_shape = (B, 1, cfg.num_codebooks) if cfg.num_codebooks > 1 else (B, 1)
    tokens = jax.random.randint(KEY, tok_shape, 0, cfg.vocab_size)
    pos = jnp.zeros((B,), jnp.int32)
    logits, new_caches = model.apply_decode(params, cfg, tokens, caches, pos)
    assert logits.shape[:2] == (B, 1)
    assert not bool(jnp.any(jnp.isnan(logits)))
    # cache structure preserved
    assert jax.tree_util.tree_structure(caches) == \
        jax.tree_util.tree_structure(new_caches)


@pytest.mark.parametrize("arch", ["yi-6b", "deepseek-v2-236b", "rwkv6-7b",
                                  "zamba2-1.2b", "deepseek-v2-lite"])
def test_prefill_decode_consistency(arch):
    """Iterated decode must reproduce the prefill logits step by step —
    the strongest end-to-end correctness check of cache semantics."""
    cfg = get_smoke_config(arch)
    params = model.init(jax.random.PRNGKey(1), cfg)
    t = 8
    tokens = jax.random.randint(jax.random.PRNGKey(2), (B, t), 0,
                                cfg.vocab_size)
    logits_pre, _ = model.apply_train(params, cfg, tokens)

    caches = model.init_caches(cfg, B, t, jnp.float32)
    outs = []
    for i in range(t):
        pos = jnp.full((B,), i, jnp.int32)
        lo, caches = model.apply_decode(params, cfg, tokens[:, i:i + 1],
                                        caches, pos)
        outs.append(lo[:, 0])
    logits_dec = jnp.stack(outs, axis=1)
    np.testing.assert_allclose(np.asarray(logits_dec),
                               np.asarray(logits_pre), atol=2e-2, rtol=2e-2)


def test_proxy_scores_in_unit_interval():
    cfg = get_smoke_config("smollm-360m")
    params = model.init(KEY, cfg)
    tokens = jax.random.randint(KEY, (4, S), 0, cfg.vocab_size)
    scores = model.proxy_scores(params, cfg, tokens)
    assert scores.shape == (4,)
    assert float(scores.min()) >= 0.0 and float(scores.max()) <= 1.0


def test_long_500k_applicability_rules():
    long = [s for s in SHAPES if s.name == "long_500k"][0]
    runs = {a: shape_applicable(get_config(a), long)[0] for a in ARCH_IDS}
    assert runs["rwkv6-7b"] and runs["zamba2-1.2b"]
    assert not runs["yi-6b"] and not runs["chameleon-34b"]
    assert sum(runs.values()) == 2


def test_param_counts_match_published():
    expected = {"yi-6b": 6.1e9, "deepseek-7b": 6.9e9, "rwkv6-7b": 7.6e9,
                "chameleon-34b": 34.3e9, "deepseek-v2-236b": 236e9,
                "llama4-maverick-400b-a17b": 398e9, "zamba2-1.2b": 1.2e9,
                "smollm-360m": 0.36e9, "deepseek-v2-lite": 15.7e9}
    for arch, n in expected.items():
        got = get_config(arch).param_count()
        assert abs(got - n) / n < 0.05, f"{arch}: {got:.3g} vs {n:.3g}"


def test_deepseek_v2_lite_published_widths():
    cfg = get_config("deepseek-v2-lite")
    assert (cfg.num_layers, cfg.d_model, cfg.num_heads) == (27, 2048, 16)
    assert (cfg.kv_lora_rank, cfg.q_lora_rank) == (512, 0)
    assert (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
            cfg.v_head_dim) == (128, 64, 128)
    assert (cfg.num_experts, cfg.held_experts, cfg.num_experts_per_tok,
            cfg.num_shared_experts, cfg.moe_d_ff) == (64, 64, 6, 2, 1408)
    assert (cfg.dense_d_ff, cfg.first_k_dense, cfg.vocab_size) == (
        10944, 1, 102400)
    assert not cfg.norm_topk_prob and cfg.routed_scaling_factor == 1.0
    assert not cfg.tie_embeddings


def _yarn_inv_freq_formula(dim, base, factor, orig, beta_fast, beta_slow):
    """DeepSeek-V2's YaRN inverse frequencies, written out in float64."""
    def corr(rot):
        return dim * math.log(orig / (rot * 2 * math.pi)) / (
            2 * math.log(base))
    low = max(math.floor(corr(beta_fast)), 0)
    high = min(math.ceil(corr(beta_slow)), dim - 1)
    out = []
    for i in range(dim // 2):
        extra = base ** (-2.0 * i / dim)
        ramp = min(max((i - low) / (high - low), 0.0), 1.0)
        out.append(extra / factor * ramp + extra * (1.0 - ramp))
    return np.asarray(out)


def test_yarn_frequencies_and_scale_match_the_formula():
    """V2-Lite's YaRN: the blended inverse frequencies, the cos/sin scale
    (mscale / mscale_all_dim = 1) and the softmax factor
    (0.1 * 0.707 * ln 40 + 1)^2 = 1.5896, at positions 0-511. Tolerance:
    float32 rounding of frequencies and angles (angles up to 511 rad)."""
    cfg = get_config("deepseek-v2-lite")
    inv_freq, scale = layers.rope_table(cfg, 64)
    want = _yarn_inv_freq_formula(64, 10000.0, 40.0, 4096, 32.0, 1.0)
    np.testing.assert_allclose(inv_freq, want, rtol=1e-6)
    assert not np.allclose(inv_freq, layers.rope_frequencies(64, 10000.0))
    assert scale == 1.0
    assert abs(layers.yarn_softmax_scale(cfg)
               - (0.1 * 0.707 * math.log(40) + 1) ** 2) < 1e-12
    assert abs(layers.yarn_softmax_scale(cfg) - 1.5896) < 1e-4

    pos = jnp.arange(512)
    x = jax.random.normal(KEY, (1, 512, 2, 64), jnp.float32)
    got = np.asarray(layers.apply_rope(x, pos[None], cfg), np.float64)
    ang = np.arange(512)[:, None] * want[None, :]
    cos, sin = np.cos(ang)[None, :, None], np.sin(ang)[None, :, None]
    x64 = np.asarray(x, np.float64)
    ref = np.concatenate([x64[..., :32] * cos - x64[..., 32:] * sin,
                          x64[..., 32:] * cos + x64[..., :32] * sin], -1)
    np.testing.assert_allclose(got, ref, atol=2e-4)


def test_rope_without_scaling_is_bit_equal_to_plain_theta():
    """No rope scaling: apply_rope is the plain-theta rotation, bit for
    bit (the formula as it stood before YaRN)."""
    cfg = get_config("smollm-360m")
    assert not cfg.yarn_factor
    x = jax.random.normal(KEY, (2, 64, 3, 64), jnp.bfloat16)
    pos = jnp.broadcast_to(jnp.arange(64)[None], (2, 64))

    def plain(x, positions, theta):
        half = x.shape[-1] // 2
        freqs = jnp.asarray(layers.rope_frequencies(x.shape[-1], theta))
        ang = positions.astype(jnp.float32)[..., None] * freqs
        cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
        xf1 = x[..., :half].astype(jnp.float32)
        xf2 = x[..., half:].astype(jnp.float32)
        return jnp.concatenate([xf1 * cos - xf2 * sin,
                                xf2 * cos + xf1 * sin], -1).astype(x.dtype)

    got = jax.jit(lambda x, p: layers.apply_rope(x, p, cfg))(x, pos)
    want = jax.jit(lambda x, p: plain(x, p, cfg.rope_theta))(x, pos)
    assert np.array_equal(np.asarray(got), np.asarray(want))


def test_proxy_scores_read_the_last_position_of_apply_train():
    """The head applied at the last position only gives the scores that
    the full-sequence logits give there."""
    for arch in ("smollm-360m", "deepseek-v2-lite", "musicgen-medium"):
        cfg = get_smoke_config(arch)
        params = model.init(KEY, cfg)
        shape = (3, S, cfg.num_codebooks) if cfg.num_codebooks > 1 else (3, S)
        tokens = jax.random.randint(KEY, shape, 0, cfg.vocab_size)
        full, _ = model.apply_train(params, cfg, tokens)
        last, _ = model.last_logits(params, cfg, tokens)
        np.testing.assert_allclose(np.asarray(last), np.asarray(full[:, -1]),
                                   atol=1e-5, rtol=1e-5)


def test_moe_loss_is_differentiable_and_counts_held_assignments():
    """loss_fn's gradient flows through the grouped matmul's custom VJP;
    aux carries each MoE layer's held assignments and largest group."""
    cfg = get_smoke_config("deepseek-v2-lite")
    params = model.init(KEY, cfg)
    tokens = jax.random.randint(KEY, (B, S), 0, cfg.vocab_size)
    grads = jax.grad(lambda p: model.loss_fn(p, cfg, tokens, tokens)[0])(
        params)
    g_moe = grads["body"]["moe_blocks"]["moe"]
    assert all(bool(jnp.all(jnp.isfinite(g))) for g in jax.tree.leaves(grads))
    assert float(jnp.abs(g_moe["w_gate"]).max()) > 0
    _, aux = model.apply_train(params, cfg, tokens)
    n_moe = cfg.num_layers - cfg.first_k_dense
    held = np.asarray(aux["held_assignments"])
    largest = np.asarray(aux["largest_held_group"])
    assert held.shape == largest.shape == (n_moe,)
    assert np.all(held <= B * S * cfg.num_experts_per_tok)
    assert np.all(largest <= held) and np.all(largest * 4 >= held)
    full = dataclasses.replace(cfg, num_held_experts=0)
    _, aux_all = model.apply_train(model.init(KEY, full), full, tokens)
    assert np.all(np.asarray(aux_all["held_assignments"])
                  == B * S * cfg.num_experts_per_tok)
