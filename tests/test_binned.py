"""Binned sketch + distributed selection plane tests (1-device mesh)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import binned, distributed
from repro.launch.mesh import make_test_mesh


def test_sketch_totals():
    rng = np.random.default_rng(0)
    s = rng.beta(0.2, 1, 10_000).astype(np.float32)
    sk = binned.build_sketch(jnp.asarray(s), 512)
    assert float(sk.total) == 10_000
    assert float(jnp.sum(sk.sum_a)) == pytest.approx(float(s.sum()), rel=1e-4)
    assert float(jnp.sum(sk.sum_w)) == pytest.approx(
        float(np.sqrt(s).sum()), rel=1e-4)


def test_sketch_sentinel_parity_across_backends():
    """The -1 "unscored" sentinel must be masked identically by the kernel
    and jnp fallback paths (the fallback used to clip it into bin 0), so
    partially-scored ScoreStore shards agree across backends."""
    rng = np.random.default_rng(7)
    s = rng.beta(0.3, 1.5, 8_192).astype(np.float32)
    s[rng.integers(0, s.shape[0], 2_000)] = -1.0
    n_valid = int((s >= 0).sum())
    sk_k = binned.build_sketch(jnp.asarray(s), 512, use_kernel=True)
    sk_j = binned.build_sketch(jnp.asarray(s), 512, use_kernel=False)
    for a, b in zip(sk_k, sk_j):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-3)
    assert float(sk_j.total) == n_valid
    # normalizers agree too — the engine's cached sampling state depends
    # on them, never on re-reducing raw shards
    np.testing.assert_allclose(
        np.asarray(binned.weight_normalizers(sk_k)),
        np.asarray(binned.weight_normalizers(sk_j)), rtol=1e-5)


def test_rank_to_threshold_conservative():
    rng = np.random.default_rng(1)
    s = rng.random(50_000).astype(np.float32)
    sk = binned.build_sketch(jnp.asarray(s), 1024)
    for rank in (10, 500, 5000):
        tau = float(binned.rank_to_threshold(sk, rank))
        assert (s >= tau).sum() >= rank    # superset guarantee


def test_selection_size_upper_bound():
    s = np.linspace(0, 1, 10_000).astype(np.float32)
    sk = binned.build_sketch(jnp.asarray(s), 1000)
    assert float(binned.selection_size(sk, 0.5)) >= (s >= 0.5).sum()


def test_merge():
    a = binned.build_sketch(jnp.asarray([0.1, 0.2]), 64)
    b = binned.build_sketch(jnp.asarray([0.9]), 64)
    m = binned.merge_sketches(a, b)
    assert float(m.total) == 3


def test_global_sketch_matches_local():
    mesh = make_test_mesh((1, 1))
    rng = np.random.default_rng(2)
    scores = jnp.asarray(rng.beta(0.1, 1, 4096).astype(np.float32))
    sk_d = distributed.global_sketch(mesh, scores, 256)
    sk_l = binned.build_sketch(scores, 256)
    np.testing.assert_allclose(np.asarray(sk_d.counts),
                               np.asarray(sk_l.counts))


def test_two_level_sampler_mass():
    totals = jnp.asarray([[10.0, 100.0], [30.0, 100.0]])  # (shard, [w, n])
    ids, _ = distributed.two_level_sample(jax.random.PRNGKey(0), totals,
                                          20_000, kappa=0.0)
    frac = float((np.asarray(ids) == 1).mean())
    assert frac == pytest.approx(0.75, abs=0.02)


def test_local_selection_count():
    mesh = make_test_mesh((1, 1))
    scores = jnp.asarray(np.linspace(0, 1, 1000).astype(np.float32))
    cnt = distributed.global_selection_count(mesh, scores, 0.25)
    assert float(cnt) == (np.linspace(0, 1, 1000) >= 0.25).sum()


def test_build_sketch_raises_on_tpu_for_untiled_bins(monkeypatch):
    """On a TPU the default sketch path is the compiled kernel: a bin count
    its tiling cannot take raises instead of silently taking the jnp
    reference (off TPU that reference still covers such counts)."""
    s = jnp.linspace(0.0, 1.0, 2048)
    assert float(binned.build_sketch(s, 1000).total) == 2048
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(ValueError, match="num_bins"):
        binned.build_sketch(s, 1000)
