"""Per-kernel allclose sweeps against the pure-jnp oracles (interpret mode)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention import ops as fa_ops
from repro.kernels.linear_scan import ops as ls_ops
from repro.kernels.score_hist import ops as sh_ops
from repro.kernels.threshold_select import ops as ts_ops
from repro.kernels.threshold_select import ref as ts_ref


# --------------------------------------------------------------------------
# flash attention
# --------------------------------------------------------------------------

@pytest.mark.parametrize("b,h,kv,s,dh", [
    (1, 4, 4, 128, 64),     # MHA
    (2, 8, 2, 256, 64),     # GQA group 4
    (1, 6, 1, 128, 128),    # MQA
])
def test_flash_attention_matches_ref(b, h, kv, s, dh):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (b, s, h, dh), jnp.float32)
    k = jax.random.normal(ks[1], (b, s, kv, dh), jnp.float32)
    v = jax.random.normal(ks[2], (b, s, kv, dh), jnp.float32)
    o_k = fa_ops.flash_attention(q, k, v, block_q=64, block_k=64)
    o_r = fa_ops.flash_attention(q, k, v, backend="ref")
    np.testing.assert_allclose(np.asarray(o_k), np.asarray(o_r),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5),
                                       (jnp.bfloat16, 2e-2)])
def test_flash_attention_dtypes(dtype, tol):
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (1, 128, 4, 64), dtype)
    k = jax.random.normal(ks[1], (1, 128, 2, 64), dtype)
    v = jax.random.normal(ks[2], (1, 128, 2, 64), dtype)
    o_k = fa_ops.flash_attention(q, k, v, block_q=64, block_k=64)
    o_r = fa_ops.flash_attention(q, k, v, backend="ref")
    np.testing.assert_allclose(np.asarray(o_k, np.float32),
                               np.asarray(o_r, np.float32), atol=tol,
                               rtol=tol)


def test_flash_attention_noncausal():
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    q = jax.random.normal(ks[0], (1, 128, 2, 64))
    k = jax.random.normal(ks[1], (1, 128, 2, 64))
    v = jax.random.normal(ks[2], (1, 128, 2, 64))
    o_k = fa_ops.flash_attention(q, k, v, causal=False, block_q=64,
                                 block_k=64)
    o_r = fa_ops.flash_attention(q, k, v, causal=False, backend="ref")
    np.testing.assert_allclose(np.asarray(o_k), np.asarray(o_r), atol=2e-5)


# --------------------------------------------------------------------------
# linear scan
# --------------------------------------------------------------------------

def _scan_inputs(key, b, h, s, dk, dv, decay_shift):
    ks = jax.random.split(key, 5)
    q = jax.random.normal(ks[0], (b, h, s, dk)) * 0.5
    k = jax.random.normal(ks[1], (b, h, s, dk)) * 0.5
    v = jax.random.normal(ks[2], (b, h, s, dv)) * 0.5
    w = jax.nn.sigmoid(jax.random.normal(ks[3], (b, h, s, dk))
                       + decay_shift)
    u = jax.random.normal(ks[4], (h, dk)) * 0.3
    return q, k, v, w, u


@pytest.mark.parametrize("bonus", [False, True])
@pytest.mark.parametrize("chunk", [16, 32, 64])
def test_linear_scan_matches_recurrence(bonus, chunk):
    q, k, v, w, u = _scan_inputs(jax.random.PRNGKey(0), 2, 2, 128, 16, 24,
                                 2.5)
    uu = u if bonus else None
    o_k, s_k = ls_ops.linear_scan(q, k, v, w, uu, chunk=chunk)
    o_r, s_r = ls_ops.linear_scan(q, k, v, w, uu, backend="ref")
    np.testing.assert_allclose(np.asarray(o_k), np.asarray(o_r), atol=1e-4)
    np.testing.assert_allclose(np.asarray(s_k), np.asarray(s_r), atol=1e-4)


def test_linear_scan_envelope_boundary():
    """Decays at the documented floor (w >= 0.1) still match the oracle."""
    q, k, v, w, u = _scan_inputs(jax.random.PRNGKey(1), 1, 2, 256, 16, 16,
                                 0.0)
    w = jnp.clip(w, 0.1, 1.0)
    o_k, _ = ls_ops.linear_scan(q, k, v, w, u, chunk=32)
    o_r, _ = ls_ops.linear_scan(q, k, v, w, u, backend="ref")
    np.testing.assert_allclose(np.asarray(o_k), np.asarray(o_r), atol=1e-3)


def test_linear_scan_out_of_envelope_is_finite():
    q, k, v, w, u = _scan_inputs(jax.random.PRNGKey(2), 1, 1, 128, 8, 8,
                                 -3.0)
    o_k, s_k = ls_ops.linear_scan(q, k, v, w, u, chunk=32)
    assert bool(jnp.all(jnp.isfinite(o_k)))
    assert bool(jnp.all(jnp.isfinite(s_k)))


# --------------------------------------------------------------------------
# score hist
# --------------------------------------------------------------------------

@pytest.mark.parametrize("n,bins", [(4096, 512), (10_000, 4096), (777, 512)])
def test_score_hist_matches_ref(n, bins):
    s = jax.random.beta(jax.random.PRNGKey(0), 0.1, 1.0, (n,))
    out_k = sh_ops.score_hist(s, bins, block_n=1024)
    out_r = sh_ops.score_hist(s, bins, backend="ref")
    for a, b in zip(out_k, out_r):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-3)


def test_score_hist_total_count():
    s = jax.random.uniform(jax.random.PRNGKey(1), (5000,))
    counts, _, _ = sh_ops.score_hist(s, 512)
    assert float(jnp.sum(counts)) == 5000


# --------------------------------------------------------------------------
# threshold select (streaming emission pass)
# --------------------------------------------------------------------------

@pytest.mark.parametrize("n", [1, 777, 1024, 4096, 10_000])
@pytest.mark.parametrize("tau", [0.0, 0.3, 0.999, 1.0])
def test_threshold_select_matches_ref(n, tau):
    """Interpret-mode kernel == numpy nonzero reference, bit-for-bit,
    including the -1 "unscored" sentinel mask and block padding."""
    rng = np.random.default_rng(n)
    s = rng.random(n).astype(np.float32)
    s[rng.integers(0, n, max(n // 10, 1))] = -1.0   # unscored sentinels
    out_k = ts_ops.threshold_select(s, tau, backend="interpret")
    out_r = ts_ref.threshold_select_ref(s, tau)
    np.testing.assert_array_equal(out_k, out_r)
    assert out_k.dtype == np.int64
    # ascending, valid, and count-consistent with a direct mask
    assert np.all(np.diff(out_k) > 0)
    assert out_k.size == int(((s >= tau) & (s >= 0)).sum())


def test_threshold_select_never_selects_sentinel():
    """Even at tau <= 0 the sentinel (-1) must never be selected."""
    s = np.asarray([-1.0, 0.0, 0.5, -1.0, 1.0], np.float32)
    for backend in ("interpret", "ref"):
        out = ts_ops.threshold_select(s, 0.0, backend=backend)
        np.testing.assert_array_equal(out, [1, 2, 4])


def test_threshold_select_edge_cases():
    assert ts_ops.threshold_select(np.empty(0, np.float32), 0.5).size == 0
    all_sel = ts_ops.threshold_select(
        np.full(2048, 0.9, np.float32), 0.5, backend="interpret")
    np.testing.assert_array_equal(all_sel, np.arange(2048))
    none_sel = ts_ops.threshold_select(
        np.full(2048, 0.1, np.float32), 0.5, backend="interpret")
    assert none_sel.size == 0


def test_threshold_select_non_tile_aligned_block_falls_back():
    """A block_n the (8, 128) tiling does not cover no longer falls back to
    the numpy reference: a kernel backend raises, so a caller always knows
    which path ran; the reference itself takes any block_n (same contract
    as score_hist)."""
    s = np.random.default_rng(0).random(1000).astype(np.float32)
    with pytest.raises(ValueError, match="block_n"):
        ts_ops.threshold_select(s, 0.5, block_n=300, backend="interpret")
    np.testing.assert_array_equal(
        ts_ops.threshold_select(s, 0.5, block_n=1024, backend="interpret"),
        ts_ref.threshold_select_ref(s, 0.5))
    out = ts_ops.threshold_select(s, 0.5, block_n=300, backend="ref")
    np.testing.assert_array_equal(out, ts_ref.threshold_select_ref(s, 0.5))


@pytest.mark.parametrize("num_bins,block_n", [(1000, 8192), (4096, 300)])
def test_score_hist_rejects_untiled_layout(num_bins, block_n):
    """The sketch kernel raises for sizes its tiling cannot take instead of
    rerouting to the jnp reference."""
    s = jnp.linspace(0.0, 1.0, 2048)
    with pytest.raises(ValueError, match="score_hist kernel"):
        sh_ops.score_hist(s, num_bins, backend="interpret", block_n=block_n)


def test_threshold_select_memmap_chunk(tmp_path):
    """The reference path operates on memmap chunks without copying."""
    p = tmp_path / "chunk.f32"
    arr = np.memmap(p, np.float32, "w+", shape=(5000,))
    arr[:] = np.random.default_rng(1).random(5000)
    out = ts_ops.threshold_select(arr[1000:3000], 0.7, backend="ref")
    np.testing.assert_array_equal(
        out, np.nonzero(np.asarray(arr[1000:3000]) >= 0.7)[0])
