"""Per-kernel allclose sweeps against the pure-jnp oracles (interpret mode)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention import ops as fa_ops
from repro.kernels.linear_scan import ops as ls_ops
from repro.kernels.moe_combine import moe_combine as combine_kernel
from repro.kernels.moe_combine import ops as combine_ops
from repro.kernels.moe_gmm import moe_gmm as gmm_kernel
from repro.kernels.moe_gmm import ops as gmm_ops
from repro.kernels.moe_gmm import ref as gmm_ref
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


# Group sizes with empty groups, groups that are no multiple of the row
# tile (16 here) and groups that share a tile; the last case leaves rows
# past the last group.
GMM_GROUPS = [[5, 0, 17, 3, 39], [0, 0, 64, 0], [16, 16, 32, 0],
              [1, 2, 3, 4, 5, 6, 7], [0, 0, 0]]


@pytest.mark.parametrize("sizes", GMM_GROUPS)
@pytest.mark.parametrize("k,n", [(32, 48), (256, 384)])
def test_moe_gmm_matches_ragged_dot_and_the_loop(sizes, k, n):
    """Pallas (interpret) and ragged_dot against the loop over groups, on
    the grouped rows. Tolerance: float32 products summed in another
    order, k terms of unit-variance operands."""
    m = 64
    rng = np.random.default_rng(len(sizes) * k)
    lhs = jnp.asarray(rng.normal(size=(m, k)), jnp.float32)
    rhs = jnp.asarray(rng.normal(size=(len(sizes), k, n)), jnp.float32)
    gs = jnp.asarray(sizes, jnp.int32)
    rows = sum(sizes)
    want = gmm_ref.gmm_ref(lhs, rhs, sizes)[:rows]
    pallas = np.asarray(gmm_kernel.gmm(lhs, rhs, gs, tm=16, interpret=True))
    ragged = np.asarray(gmm_ops.moe_gmm(lhs, rhs, gs))
    np.testing.assert_allclose(pallas[:rows], want, atol=1e-4 * k ** 0.5)
    np.testing.assert_allclose(ragged[:rows], want, atol=1e-4 * k ** 0.5)


def test_moe_gmm_tiles_divide_the_published_widths():
    """V2-Lite's gate-up (2048 -> 2 x 1408) and down (1408 -> 2048)."""
    assert [gmm_kernel.tile(d) for d in (2048, 2816, 1408)] == [
        1024, 1408, 1408]
    assert gmm_kernel.tile(96) == 96          # no multiple of 128: one block


def test_moe_gmm_gradient_is_ragged_dots():
    """The custom VJP gives ragged_dot's gradients for lhs and rhs."""
    rng = np.random.default_rng(3)
    lhs = jnp.asarray(rng.normal(size=(40, 24)), jnp.float32)
    rhs = jnp.asarray(rng.normal(size=(3, 24, 8)), jnp.float32)
    gs = jnp.asarray([10, 0, 25], jnp.int32)
    ct = jnp.asarray(rng.normal(size=(40, 8)), jnp.float32).at[35:].set(0)

    def loss(f):
        return lambda a, b: jnp.sum(f(a, b) * ct)
    got = jax.grad(loss(lambda a, b: gmm_ops.moe_gmm(a, b, gs)),
                   argnums=(0, 1))(lhs, rhs)
    want = jax.grad(loss(lambda a, b: jax.lax.ragged_dot(a, b, gs)),
                    argnums=(0, 1))(lhs, rhs)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=1e-5)


# Routings of 64 tokens over 16 experts, 4 of them held from `first`,
# combined in tiles of 16 tokens and windows of 8 rows: (k, first, how).
# "all_held" is the worst case, every range a whole tile of 16 rows;
# "over_a_window" sends every token to the first held expert, 16 rows a
# tile against a window of 8; "top1" is Maverick's top-1.
COMBINE_CASES = {"random": (4, 0, "random"), "all_held": (4, 0, "held"),
                 "none_held": (4, 0, "none"),
                 "over_a_window": (4, 0, "first"),
                 "first_nonzero": (4, 8, "random"),
                 "top1": (1, 0, "random")}
COMBINE_TOKENS, COMBINE_EXPERTS, COMBINE_HELD = 64, 16, 4


def _routing(k, first, how, rng):
    held = np.arange(first, first + COMBINE_HELD)
    rest = np.setdiff1d(np.arange(COMBINE_EXPERTS), held)
    pick = {"random": lambda: rng.permutation(COMBINE_EXPERTS)[:k],
            "held": lambda: rng.permutation(held)[:k],
            "none": lambda: rng.permutation(rest)[:k],
            "first": lambda: np.r_[first, rng.permutation(rest)[:k - 1]]}
    return np.stack([pick[how]() for _ in range(COMBINE_TOKENS)])


def _counting_sort(ids, first):
    """The dispatch's rows, plainly: each held expert's assignments in
    token order, one expert after another; (pos, row_token, sizes)."""
    n, k = ids.shape
    rows = n * min(k, COMBINE_HELD)
    pos = np.full((n, k), rows, np.int32)
    row_token = np.zeros(rows, np.int32)
    sizes = np.zeros(COMBINE_HELD, np.int32)
    r = 0
    for e in range(COMBINE_HELD):
        for t, j in zip(*np.nonzero(ids == first + e)):
            pos[t, j], row_token[r] = r, t
            sizes[e] += 1
            r += 1
    return pos, row_token, sizes


def _combine_inputs(case, d=128):
    k, first, how = COMBINE_CASES[case]
    rng = np.random.default_rng(sorted(COMBINE_CASES).index(case))
    pos, row_token, sizes = _counting_sort(_routing(k, first, how, rng),
                                           first)
    y = rng.normal(size=(pos.shape[0] * min(k, COMBINE_HELD), d))
    y[sizes.sum():] = np.nan              # rows the grouped matmul leaves
    gates = rng.random(pos.shape)
    return (jnp.asarray(y, jnp.bfloat16), jnp.asarray(pos),
            jnp.asarray(gates, jnp.float32), jnp.asarray(row_token),
            jnp.asarray(sizes))


@pytest.mark.parametrize("case", sorted(COMBINE_CASES))
def test_moe_combine_matches_the_slot_loop(case):
    """Pallas (interpret) and the slot-major jnp combine against a loop
    over each token's k slots; rows past the groups hold NaN and are never
    read. Tolerance: float32 products of bf16 rows, at most k summed."""
    y, pos, gates, row_token, sizes = _combine_inputs(case)
    y64, g64 = np.asarray(y, np.float64), np.asarray(gates, np.float64)
    want = np.zeros((pos.shape[0], y.shape[1]))
    for t, j in np.ndindex(*pos.shape):
        if pos[t, j] < y.shape[0]:
            want[t] += g64[t, j] * y64[pos[t, j]]
    pallas = np.asarray(combine_kernel.combine(
        y, pos, gates, row_token, sizes, tt=16, tr=8, interpret=True))
    jnp_path = np.asarray(combine_ops.moe_combine(y, pos, gates, row_token,
                                                  sizes))
    for got in (pallas, jnp_path):
        assert got.dtype == np.float32 and np.isfinite(got).all()
        np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)


def test_moe_combine_tiles_fit_the_published_widths():
    """Token tiles of V2-Lite's block (d 2,048) and Maverick's (d 5,120)
    keep the float32 output tile within 4 MiB; a token count no multiple
    of 8 divides is one tile."""
    assert combine_kernel.tile_t(16384, 2048) == 512
    assert combine_kernel.tile_t(16384, 5120) == 128
    assert combine_kernel.tile_t(24, 2048) == 8
    assert combine_kernel.tile_t(6, 2048) == 6


def test_moe_combine_gradient_is_the_slot_loops():
    """The custom VJP gives the jnp combine's gradients: each held row
    gets its token's cotangent times the gate, each gate the cotangent's
    product with its row."""
    y, pos, gates, row_token, sizes = _combine_inputs("random", d=16)
    y = jnp.nan_to_num(y.astype(jnp.float32))
    ct = jnp.asarray(np.random.default_rng(3).normal(size=(64, 16)),
                     jnp.float32)
    got_y, got_g = jax.grad(
        lambda a, b: jnp.sum(combine_ops.moe_combine(
            a, pos, b, row_token, sizes) * ct), argnums=(0, 1))(y, gates)
    want_y, want_g = np.zeros(y.shape), np.zeros(gates.shape)
    for t, j in np.ndindex(*pos.shape):
        if pos[t, j] < y.shape[0]:
            want_y[pos[t, j]] += gates[t, j] * ct[t]
            want_g[t, j] = np.dot(ct[t], y[pos[t, j]])
    np.testing.assert_allclose(np.asarray(got_y), want_y, atol=1e-5)
    np.testing.assert_allclose(np.asarray(got_g), want_g, atol=1e-5)
