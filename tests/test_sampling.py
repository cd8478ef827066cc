"""Tests for uniform / importance samplers (Theorem-1 weights)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import sampling


def test_sqrt_weights_normalized_and_defensive():
    scores = jnp.asarray(np.random.default_rng(0).beta(0.1, 1, 1000),
                         jnp.float32)
    w = sampling.sqrt_proxy_weights(scores)
    assert float(jnp.sum(w)) == pytest.approx(1.0, abs=1e-4)
    # defensive floor: every record keeps >= kappa/n mass
    assert float(jnp.min(w)) >= 0.1 / 1000 * 0.999


def test_degenerate_all_zero_scores_fall_back_to_uniform():
    w = sampling.sqrt_proxy_weights(jnp.zeros(100))
    np.testing.assert_allclose(np.asarray(w), 1 / 100, rtol=1e-5)


def test_inverse_cdf_distribution():
    """Draw frequencies converge to the target probabilities."""
    probs = jnp.asarray([0.5, 0.25, 0.125, 0.125])
    s = 40_000
    ws = sampling.sample_weighted(jax.random.PRNGKey(0), probs, s)
    freq = np.bincount(np.asarray(ws.indices), minlength=4) / s
    np.testing.assert_allclose(freq, np.asarray(probs), atol=0.02)


def test_reweighting_unbiased():
    """E[O(x) m(x)] over a weighted sample == population mean of O."""
    rng = np.random.default_rng(1)
    n = 50_000
    scores = rng.beta(0.05, 1, n).astype(np.float32)
    labels = (rng.random(n) < scores).astype(np.float32)
    ws = sampling.draw_oracle_sample(jax.random.PRNGKey(2),
                                     jnp.asarray(scores), 20_000,
                                     scheme="sqrt")
    est = float(np.mean(labels[np.asarray(ws.indices)] * np.asarray(ws.m)))
    assert est == pytest.approx(float(labels.mean()), rel=0.15)


def test_sqrt_beats_uniform_variance_on_calibrated_proxy():
    """Theorem 1: sqrt weights reduce the estimator variance vs uniform."""
    rng = np.random.default_rng(2)
    n, s, reps = 200_000, 2000, 30
    scores = rng.beta(0.01, 1, n).astype(np.float32)
    labels = (rng.random(n) < scores).astype(np.float32)
    sj = jnp.asarray(scores)

    def estimates(scheme, seed0):
        vals = []
        for t in range(reps):
            ws = sampling.draw_oracle_sample(
                jax.random.PRNGKey(seed0 + t), sj, s, scheme=scheme)
            vals.append(np.mean(labels[np.asarray(ws.indices)]
                                * np.asarray(ws.m)))
        return np.var(vals)

    assert estimates("sqrt", 0) < estimates("uniform", 1000)


def test_masked_sampling_stays_in_mask():
    scores = jnp.linspace(0, 1, 1000)
    mask = (scores >= 0.8).astype(jnp.float32)
    ws = sampling.sample_weighted_masked(jax.random.PRNGKey(3),
                                         jnp.ones(1000), mask, 500)
    assert np.all(np.asarray(ws.indices) >= 800)


@given(st.integers(10, 2000), st.integers(1, 500))
@settings(max_examples=20, deadline=None)
def test_uniform_sample_shape_and_m(n, s):
    ws = sampling.sample_uniform(jax.random.PRNGKey(0), n, s)
    assert ws.indices.shape == (s,)
    assert np.all(np.asarray(ws.indices) < n)
    np.testing.assert_allclose(np.asarray(ws.m), 1.0)


# -- hierarchical chunk-mass primitives --------------------------------------

def test_chunk_raw_masses_ignore_sentinels():
    rng = np.random.default_rng(7)
    scores = rng.random(5000).astype(np.float32)
    scores[::7] = -1.0                         # unscored sentinel
    s_sqrt, s_a, b_sqrt, b_a = sampling.chunk_raw_masses(scores)
    a = np.clip(scores, 0.0, 1.0)              # sentinel clips to 0 raw mass
    assert s_sqrt == pytest.approx(float(np.sum(np.sqrt(a), dtype=np.float64)))
    assert s_a == pytest.approx(float(np.sum(a, dtype=np.float64)))
    # per 1,024-record block, the last one short (5000 = 4·1024 + 904)
    starts = np.arange(0, 5000, sampling.BLOCK_RECORDS)
    assert b_sqrt.size == b_a.size == 5
    np.testing.assert_allclose(
        b_sqrt, [np.sum(np.sqrt(a[i:i + 1024]), dtype=np.float64)
                 for i in starts], rtol=1e-12)
    np.testing.assert_allclose(
        b_a, [np.sum(a[i:i + 1024], dtype=np.float64) for i in starts],
        rtol=1e-12)


def test_defensive_chunk_mass_is_sum_of_record_probs():
    """A chunk's defensive mass from the cached raw sums must equal the sum
    of its records' p(x) — the identity that makes the hierarchical draw
    reproduce the dense defensive mixture exactly."""
    rng = np.random.default_rng(8)
    n_total, kappa = 20_000, 0.1
    scores = rng.beta(0.3, 1.0, n_total).astype(np.float32)
    z = float(np.sum(np.sqrt(scores), dtype=np.float64))
    chunks = np.array_split(scores, 7)
    sizes = np.asarray([c.shape[0] for c in chunks], np.int64)
    raws = np.asarray([sampling.chunk_raw_masses(c)[0] for c in chunks])
    masses = sampling.defensive_chunk_mass(raws, sizes, z, kappa, n_total)
    for c, m in zip(chunks, masses):
        p = sampling.defensive_probs(c, "sqrt", z, kappa, n_total)
        assert float(np.sum(p, dtype=np.float64)) == pytest.approx(m,
                                                                   rel=1e-5)
    # all chunk masses together carry the whole defensive mixture
    assert float(masses.sum()) == pytest.approx(1.0, rel=1e-5)


def test_defensive_probs_match_dense_formula():
    """defensive_probs must be bit-identical to the dense per-record
    formula (float32), for both schemes."""
    rng = np.random.default_rng(9)
    scores = rng.random(4096).astype(np.float32)
    n_total, kappa, z = 100_000, 0.1, 777.5
    for scheme in ("sqrt", "prop"):
        a = np.clip(scores, 0.0, 1.0)
        raw = np.sqrt(a) if scheme == "sqrt" else a
        dense = ((1.0 - kappa) * raw / z + kappa / n_total).astype(np.float32)
        got = sampling.defensive_probs(scores, scheme, z, kappa, n_total)
        np.testing.assert_array_equal(got, dense)
