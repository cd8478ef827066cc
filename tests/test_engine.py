"""SelectionEngine data-plane tests: cached-state sampling, vectorized
gathers, regression fixes, run_many batching, streamed-vs-materialized
equivalence, partially-scored stores, and equivalence against the
single-host exact path."""
import numpy as np
import pytest

import jax

from repro.core import queries
from repro.core.engine import SelectionEngine, ShardedSelection
from repro.core.oracle import array_oracle
from repro.core.queries import JointSUPGQuery, SUPGQuery
from repro.data.pipeline import (BitmaskStore, CallbackSink, IndexSink,
                                 ScoreStore, SelectionStream)
from repro.data.synthetic import make_beta


# -- regression: total_selected ---------------------------------------------

def test_total_selected_is_mask_sum():
    """Regression: the seed carried a dead expression that always added 0;
    total_selected must equal the plain sum over shard masks."""
    masks = [np.array([True, False, True]), np.array([False, True])]
    sel = ShardedSelection(masks=masks, tau=0.5, oracle_calls=7,
                           sampled_positive_global=np.array([0, 4]))
    assert sel.total_selected == 3


# -- regression: empty shards in _uniform_in_region -------------------------

def test_uniform_in_region_excludes_empty_shards():
    """Shards whose region {A >= tau} is empty must receive zero draws —
    the seed floored their mass at 1e-30 and then clamp-returned records
    *below* tau."""
    lo = np.zeros(1000, np.float32)             # region empty at tau=0.5
    hi = np.full(500, 0.9, np.float32)
    engine = SelectionEngine([lo, hi], num_bins=512)
    idx = engine._uniform_in_region(jax.random.PRNGKey(0), 300, 0.5)
    assert np.all(idx >= 1000)                  # never from the empty shard
    assert np.all(engine.score_at(idx) >= 0.5)


def test_uniform_in_region_chunked_rank_routing():
    """The chunk-streamed region draw (O(chunk) memory) must stay uniform
    over {A >= tau} when regions span many chunks, and never select the
    unscored sentinel."""
    rng = np.random.default_rng(5)
    scores = rng.random(10_000).astype(np.float32)
    scores[rng.integers(0, 10_000, 500)] = -1.0
    engine = SelectionEngine(np.array_split(scores, 3), num_bins=512,
                             chunk_records=256)    # many chunks per shard
    idx = engine._uniform_in_region(jax.random.PRNGKey(4), 5000, 0.6)
    got = engine.score_at(idx)
    assert np.all(got >= 0.6)                      # region + sentinel safe
    # roughly uniform across the region: compare shard allocation to the
    # true per-shard region sizes
    region_per_shard = np.asarray(
        [((s >= 0.6) & (s >= 0)).sum() for s in engine.shards], np.float64)
    shd = np.searchsorted(engine.offsets, idx, side="right") - 1
    frac = np.bincount(shd, minlength=3) / 5000
    np.testing.assert_allclose(
        frac, region_per_shard / region_per_shard.sum(), atol=0.05)


def test_uniform_in_region_count_and_resolve_agree_on_float64():
    """Regression: the counting pass used to compare in float32 while the
    rank-routed resolve pass compared in the shard's native dtype; a
    float64 score inside the float32 rounding gap around tau then made the
    counted region larger than the resolved one (IndexError on the rank).
    Both passes now run the identical threshold_select backend."""
    scores = np.array([0.5000000001, 0.7] * 500, np.float64)
    engine = SelectionEngine([scores], num_bins=512, chunk_records=128)
    tau = 0.5000000002                      # rounds below 0.5000000001 in f32
    idx = engine._uniform_in_region(jax.random.PRNGKey(0), 2000, tau)
    assert np.all(scores[idx] >= tau)


def test_uniform_in_region_globally_empty_falls_back_to_uniform():
    engine = SelectionEngine([np.zeros(100, np.float32),
                              np.zeros(50, np.float32)], num_bins=512)
    idx = engine._uniform_in_region(jax.random.PRNGKey(1), 64, 0.5)
    assert idx.shape == (64,)
    assert np.all((idx >= 0) & (idx < 150))


# -- vectorized gathers ------------------------------------------------------

def test_score_at_matches_elementwise_gather():
    rng = np.random.default_rng(0)
    shards = [rng.random(n).astype(np.float32) for n in (1000, 1, 2500, 700)]
    flat = np.concatenate(shards)
    gi = rng.integers(0, flat.shape[0], 5000)
    # both gather paths: flat concatenation cache and routed per-shard
    fast = SelectionEngine(shards, num_bins=512)
    routed = SelectionEngine(shards, num_bins=512, cache_flat=False)
    assert fast._flat is not None and routed._flat is None
    np.testing.assert_array_equal(fast.score_at(gi), flat[gi])
    np.testing.assert_array_equal(routed.score_at(gi), flat[gi])


def test_fold_positives_sink_level():
    """Labeled positives below tau are folded as a sink-level merge, routed
    to their shards; positives at/above tau stream out of their own chunks
    (fold/emit disjointness keeps per-shard counts exact)."""
    shards = [np.zeros(100, np.float32), np.zeros(50, np.float32)]
    shards[1][49] = 0.9                       # above tau: emitted, not folded
    engine = SelectionEngine(shards, num_bins=512)
    pos = np.asarray([0, 99, 100, 149], np.int64)
    sel = engine._emit_selection(0.5, pos, oracle_calls=0, sink=None,
                                 chunk_records=64)
    masks = sel.masks
    assert masks[0][0] and masks[0][99] and masks[1][0] and masks[1][49]
    assert masks[0].sum() == 2 and masks[1].sum() == 2
    np.testing.assert_array_equal(sel.shard_counts, [2, 2])
    assert sel.total_selected == 4


# -- cached sampling state ---------------------------------------------------

def test_draw_sample_reweighting_unbiased_from_cache():
    """m(x) factors from the sketch-derived cached CDFs stay unbiased."""
    ds = make_beta(80_000, 0.05, 1.0, seed=6)
    engine = SelectionEngine(np.array_split(ds.scores, 3), num_bins=1024)
    idx, m = engine.draw_sample(jax.random.PRNGKey(1), 20_000, "sqrt")
    est = float(np.mean(ds.labels[idx] * m))
    assert est == pytest.approx(float(ds.labels.mean()), rel=0.2)
    # second draw hits the cache — same state object, no rebuild
    assert len(engine._sampling_cache) == 1
    engine.draw_sample(jax.random.PRNGKey(2), 100, "sqrt")
    assert len(engine._sampling_cache) == 1


def test_scorestore_shards_work_end_to_end(tmp_path):
    ds = make_beta(40_000, 0.02, 1.0, seed=8)
    halves = np.array_split(ds.scores, 2)
    stores = []
    for i, half in enumerate(halves):
        st = ScoreStore(tmp_path / f"shard{i}.scores", half.shape[0],
                        create=True)
        st.write(0, half)
        stores.append(st)
    engine = SelectionEngine(stores, num_bins=1024)
    assert engine.n_total == 40_000
    # out-of-core shards must NOT be concatenated into a RAM flat cache
    assert engine._flat is None
    q = SUPGQuery(target="recall", gamma=0.9, delta=0.05, budget=3000,
                  method="is")
    sel = engine.run(jax.random.PRNGKey(3), array_oracle(ds.labels), q)
    mask = np.concatenate(sel.masks)
    assert queries.recall_of(np.nonzero(mask)[0], ds.truth_mask()) >= 0.85
    assert sel.oracle_calls <= 3000


# -- run_many ----------------------------------------------------------------

def test_run_many_batches_rt_pt_jt():
    ds = make_beta(100_000, 0.01, 1.0, seed=12)
    engine = SelectionEngine(np.array_split(ds.scores, 4), num_bins=1024)
    oracle = array_oracle(ds.labels)
    batch = [
        SUPGQuery(target="recall", gamma=0.9, delta=0.05, budget=3000,
                  method="is"),
        SUPGQuery(target="precision", gamma=0.9, delta=0.05, budget=3000,
                  method="is"),
        JointSUPGQuery(gamma_recall=0.8, stage_budget=3000),
    ]
    results = engine.run_many(jax.random.PRNGKey(5), oracle, batch)
    assert len(results) == 3
    truth = ds.truth_mask()
    rt_mask = np.concatenate(results[0].masks)
    assert queries.recall_of(np.nonzero(rt_mask)[0], truth) >= 0.85
    pt_mask = np.concatenate(results[1].masks)
    assert queries.precision_of(np.nonzero(pt_mask)[0], truth) >= 0.8
    # JT: exhaustive filtering => precision exactly 1.0, recall from RT stage
    jt_mask = np.concatenate(results[2].masks)
    assert queries.precision_of(np.nonzero(jt_mask)[0], truth) == \
        pytest.approx(1.0)
    assert queries.recall_of(np.nonzero(jt_mask)[0], truth) >= 0.75
    # run_many batches ride one shared labeling channel: records labeled
    # for the RT/PT queries answer the JT verification stage from the
    # cache for free, so the JT query's *attributed* oracle_calls can land
    # well below its stage budget (the exhaustive verification itself is
    # evident in the exact precision above). A solo run_joint on a plain
    # callable gets a private channel: its verification labels every
    # candidate in {A >= tau}, however far past the stage budget that
    # goes, and nothing is answered by another query's labels.
    assert 0 < results[2].oracle_calls
    solo = engine.run_joint(jax.random.PRNGKey(5), oracle, batch[2])
    n_candidates = int((ds.scores >= solo.tau).sum())
    assert solo.oracle_calls >= n_candidates  # stage-3 usage is unbounded
    assert solo.oracle_calls > results[2].oracle_calls
    # budgets stay per-query for plain queries
    for r in results[:2]:
        assert r.oracle_calls <= 3000


def test_run_many_matches_independent_runs():
    """run_many is a batching device, not a semantics change: with matched
    per-query keys it returns exactly what independent run() calls do."""
    ds = make_beta(50_000, 0.02, 1.0, seed=14)
    engine = SelectionEngine(np.array_split(ds.scores, 3), num_bins=1024)
    oracle = array_oracle(ds.labels)
    qs = [SUPGQuery(target="recall", gamma=0.85, budget=2000, method="is"),
          SUPGQuery(target="precision", gamma=0.8, budget=2000,
                    method="noci")]
    key = jax.random.PRNGKey(21)
    batched = engine.run_many(key, oracle, qs)
    keys = jax.random.split(key, 2)
    for k, q, b in zip(keys, qs, batched):
        solo = engine.run(k, oracle, q)
        assert solo.tau == b.tau
        np.testing.assert_array_equal(np.concatenate(solo.masks),
                                      np.concatenate(b.masks))


# -- streamed emission: sink equivalence -------------------------------------

def _materialized_baseline(engine, sel):
    """The PR-1 behavior, computed directly: full boolean masks
    {A >= tau} (never the unscored sentinel) with labeled positives folded
    in. The streamed plane must reproduce this bit-for-bit."""
    masks = []
    for s in engine.shards:
        s = np.asarray(s, np.float32)
        masks.append((s >= sel.tau) & (s >= 0.0))
    pos = sel.sampled_positive_global
    if pos.size:
        shd = np.searchsorted(engine.offsets, pos, side="right") - 1
        for i in range(len(masks)):
            masks[i][pos[shd == i] - engine.offsets[i]] = True
    return masks


@pytest.mark.parametrize("qspec", ["rt", "pt", "jt"])
def test_streamed_selection_matches_materialized(tmp_path, qspec):
    """Streamed emission through every sink type returns exactly the PR-1
    materialized masks on RT, PT, and JT queries (same key => same tau and
    sample => identical selections, bit-for-bit)."""
    ds = make_beta(60_000, 0.02, 1.0, seed=40)
    truth_split = np.array_split(ds.labels > 0.5, 3)
    oracle = array_oracle(ds.labels)
    engine = SelectionEngine(np.array_split(ds.scores, 3), num_bins=1024,
                             chunk_records=7_000)   # force multiple chunks
    q = {"rt": SUPGQuery(target="recall", gamma=0.9, budget=2000),
         "pt": SUPGQuery(target="precision", gamma=0.8, budget=2000),
         "jt": JointSUPGQuery(gamma_recall=0.85, stage_budget=2000)}[qspec]
    key = jax.random.PRNGKey(7)

    def run(sink=None):
        if qspec == "jt":
            return engine.run_joint(key, oracle, q, sink=sink)
        return engine.run(key, oracle, q, sink=sink)

    base = run()                      # default IndexSink
    assert isinstance(base.sink, IndexSink)
    expected = _materialized_baseline(engine, base)
    if qspec == "jt":                 # verified positives only
        expected = [m & t for m, t in zip(expected, truth_split)]
    np.testing.assert_array_equal(np.concatenate(base.masks),
                                  np.concatenate(expected))
    np.testing.assert_array_equal(
        base.shard_counts, [m.sum() for m in expected])

    # memmap-packed bitmask sink
    bits = BitmaskStore(tmp_path / f"{qspec}.bits")
    sel_b = run(sink=bits)
    assert sel_b.tau == base.tau
    np.testing.assert_array_equal(np.concatenate(sel_b.masks),
                                  np.concatenate(expected))

    # callback sink: rebuild masks from the streamed chunks
    got = [[] for _ in engine.shards]
    sel_c = run(sink=CallbackSink(
        lambda sh, gids, folded: got[sh].append(gids)))
    rebuilt = []
    for sh, chunks in enumerate(got):
        m = np.zeros(engine.shards[sh].shape[0], bool)
        if chunks:
            m[np.concatenate(chunks) - engine.offsets[sh]] = True
        rebuilt.append(m)
    np.testing.assert_array_equal(np.concatenate(rebuilt),
                                  np.concatenate(expected))
    assert sel_c.total_selected == int(np.concatenate(expected).sum())


def test_selection_stream_consumes_query_incrementally():
    ds = make_beta(20_000, 0.02, 1.0, seed=41)
    engine = SelectionEngine(np.array_split(ds.scores, 2), num_bins=512,
                             chunk_records=2_000)
    q = SUPGQuery(target="recall", gamma=0.9, budget=1000)
    stream = SelectionStream(
        lambda sink: engine.run(jax.random.PRNGKey(2),
                                array_oracle(ds.labels), q, sink=sink))
    seen = 0
    for shard_id, gids, folded in stream:
        assert np.all((gids >= engine.offsets[shard_id])
                      & (gids < engine.offsets[shard_id + 1]))
        seen += gids.size
    assert stream.result.total_selected == seen > 0


# -- partially-scored stores -------------------------------------------------

def test_partially_scored_store_sketch_parity_and_selection(tmp_path):
    """A store with unscored (-1) records must sketch identically on the
    kernel and jnp paths (sentinel masked, not clipped into bin 0) and the
    streamed selection must never emit unscored records."""
    rng = np.random.default_rng(9)
    n, scored = 40_000, 30_000
    scores = rng.beta(0.5, 2.0, scored).astype(np.float32)
    store = ScoreStore(tmp_path / "partial.scores", n, create=True)
    store.write(0, scores)
    assert store.num_scored == scored

    ek = SelectionEngine([store], num_bins=512, use_kernel=True)
    ej = SelectionEngine([store], num_bins=512, use_kernel=False)
    for a, b in zip(ek.sketch, ej.sketch):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-3)
    assert float(ej.sketch.total) == scored       # sentinel not in bin 0
    assert float(ej.sketch.counts[0]) < scored

    labels = np.zeros(n, np.float32)
    labels[:scored] = (rng.random(scored) < scores).astype(np.float32)
    q = SUPGQuery(target="recall", gamma=0.85, budget=2000)
    sel = ej.run(jax.random.PRNGKey(3), array_oracle(labels), q)
    mask = np.concatenate(sel.masks)
    assert mask[:scored].any()
    assert not mask[scored:].any()                # unscored never selected
    assert sel.total_selected == int(mask.sum())


# -- hierarchical sampler: chunk-level state + dense equivalence --------------

def _dense_probs(engine, scheme):
    """The dense per-record defensive-mixture p(x) the pre-hierarchical
    engine materialized — the reference distribution for equivalence."""
    z = max(engine._z[scheme], 1e-30)
    flat = np.concatenate([np.asarray(s, np.float32) for s in engine.shards])
    a = np.clip(flat, 0.0, 1.0)
    raw = np.sqrt(a) if scheme == "sqrt" else a
    return ((1.0 - engine.kappa) * raw / z
            + engine.kappa / engine.n_total).astype(np.float32)


def test_sampling_state_is_chunk_level():
    """Persistent sampling state must be O(n / chunk_records) per
    (shard, scheme) — chunk-mass CDFs, never per-record arrays."""
    rng = np.random.default_rng(3)
    shards = [rng.random(n).astype(np.float32) for n in (9000, 100, 4096)]
    engine = SelectionEngine(shards, num_bins=512, chunk_records=1024,
                             weight_schemes=("sqrt", "prop"))
    assert len(engine._sampling_cache) == 2
    for states in engine._sampling_cache.values():
        for sh, st in enumerate(states):
            n_chunks = -(-shards[sh].shape[0] // 1024)
            assert st.cdf.size == n_chunks == engine.plan.num_chunks(sh)
            assert not hasattr(st, "p_global")
    for sh, cm in enumerate(engine._chunk_masses):
        assert cm.sizes.size == engine.plan.num_chunks(sh)
        assert int(cm.sizes.sum()) == shards[sh].shape[0]


@pytest.mark.parametrize("chunk", [1500, 3000, 4096])
def test_block_masses_partition_chunk_masses(chunk):
    """Persistent block state is ⌈chunk/1024⌉ float64 sums a chunk for
    each scheme, none straddling a chunk, adding up to the chunk sums."""
    rng = np.random.default_rng(4)
    shards = [rng.random(n).astype(np.float32) for n in (9000, 0, 100, 4096)]
    shards[0][::11] = -1.0                          # unscored sentinels
    engine = SelectionEngine(shards, num_bins=512, chunk_records=chunk)
    for sh, cm in enumerate(engine._chunk_masses):
        n_blocks = int((-(-cm.sizes // 1024)).sum())
        assert cm.block_sqrt.shape == cm.block_a.shape == (n_blocks,)
        assert cm.block_sqrt.dtype == cm.block_a.dtype == np.float64
        for ci, span in enumerate(engine.plan.shard_spans(sh)):
            nb = -(-span.size // 1024)
            for scheme in ("sqrt", "prop"):
                blocks = cm.block_raw(scheme, ci)
                assert blocks.size == nb
                np.testing.assert_allclose(blocks.sum(), cm.raw(scheme)[ci],
                                           rtol=1e-12)


def test_block_masses_append_matches_cold_build():
    """An append adds block sums for the appended shards only: the old
    shards' arrays are the same objects, and every array is bit-identical
    to a cold build over the same shards."""
    rng = np.random.default_rng(6)
    shards = [rng.beta(0.2, 1.0, n).astype(np.float32)
              for n in (5000, 3100, 0, 7000)]
    eng = SelectionEngine(shards[:2], num_bins=256, chunk_records=3000,
                          weight_schemes=("sqrt", "prop"))
    old = list(eng._chunk_masses)
    eng._append_shards(shards[2:])
    cold = SelectionEngine(shards, num_bins=256, chunk_records=3000,
                           weight_schemes=("sqrt", "prop"))
    assert all(a is b for a, b in zip(eng._chunk_masses[:2], old))
    assert len(eng._chunk_masses) == len(cold._chunk_masses) == 4
    for got, want in zip(eng._chunk_masses, cold._chunk_masses):
        for field in got._fields:
            np.testing.assert_array_equal(getattr(got, field),
                                          getattr(want, field))
    for key, states in cold._sampling_cache.items():
        for a, b in zip(eng._sampling_cache[key], states):
            np.testing.assert_array_equal(a.cdf, b.cdf)


def _whole_chunk_draw(engine, key, s, scheme):
    """`draw_sample` as it resolved a chunk before blocks: the inverse
    CDF of `defensive_probs` over the whole allocated chunk, searched
    with the same uniforms."""
    import jax.numpy as jnp

    from repro.core import sampling

    st = engine._state
    states = engine._sampling_state(scheme, engine.kappa)
    mass = engine._shard_masses(scheme, engine.kappa)
    k_alloc, k_chunk, k_rec = jax.random.split(key, 3)
    alloc = np.asarray(jax.random.categorical(
        k_alloc, jnp.log(jnp.asarray(mass, jnp.float32)), shape=(s,)))
    u_chunk = np.asarray(jax.random.uniform(k_chunk, (s,)), np.float64)
    u_rec = np.asarray(jax.random.uniform(k_rec, (s,)), np.float64)
    idx, m = np.empty(s, np.int64), np.empty(s, np.float32)
    chunk = st.plan.chunk_records
    for sh in np.unique(alloc):
        seg = np.flatnonzero(alloc == sh)
        cis = sampling.draw_from_cdf(states[sh].cdf, u_chunk[seg])
        for ci in np.unique(cis):
            pos = seg[cis == ci]
            start = ci * chunk
            p = sampling.defensive_probs(
                st.shards[sh][start:start + chunk], scheme,
                st.z[scheme], engine.kappa, st.n_total)
            local = sampling.draw_from_cdf(sampling.normalized_cdf(p),
                                           u_rec[pos])
            idx[pos] = st.offsets[sh] + start + local
            m[pos] = (1.0 / st.n_total) / np.maximum(p[local], 1e-38)
    return idx, m


@pytest.mark.parametrize("layout", ["chunk1500", "chunk3000", "scorestore",
                                    "dedup"])
@pytest.mark.parametrize("scheme", ["sqrt", "prop"])
def test_block_draw_matches_whole_chunk_draw(tmp_path, scheme, layout):
    """For a fixed key the block resolve picks the record the whole-chunk
    inverse CDF picks for at least 99.9% of draws, with the same m there
    exactly: partial blocks (chunks of 1,500 and 3,000 records), an empty
    shard, unscored sentinels, a memmap ScoreStore shard, and chunks with
    far more draws than blocks, whose records are each read once."""
    from repro.core import sampling

    rng = np.random.default_rng(31)
    chunk, s = (1500 if layout == "chunk1500" else 3000), 20_000
    shards = [rng.beta(0.1, 1.0, n).astype(np.float32)
              for n in (40_000, 0, 23_456)]
    shards[0][rng.integers(0, 40_000, 3000)] = -1.0   # unscored sentinels
    if layout == "scorestore":
        store = ScoreStore(tmp_path / "s.scores", 30_000, create=True)
        store.write(0, rng.beta(0.1, 1.0, 25_000).astype(np.float32))
        shards[1] = store                           # last 5,000 unscored
    if layout == "dedup":
        shards = [rng.beta(0.1, 1.0, 7000).astype(np.float32)]
    engine = SelectionEngine(shards, num_bins=512, chunk_records=chunk)
    key = jax.random.PRNGKey(41)
    idx, m = engine.draw_sample(key, s, scheme)
    ref_idx, ref_m = _whole_chunk_draw(engine, key, s, scheme)
    same = idx == ref_idx
    assert same.mean() >= 0.999, same.mean()
    np.testing.assert_array_equal(m[same], ref_m[same])
    if layout == "dedup":                  # ~6,700 draws a 3-block chunk
        st = engine._state
        for ci in range(engine.plan.num_chunks(0)):
            size = engine.plan.shard_spans(0)[ci].size
            got = sampling.draw_in_blocks(
                st.shards[0][ci * chunk:ci * chunk + size],
                st.chunk_masses[0].block_raw(scheme, ci),
                rng.random(s), scheme, st.z[scheme], engine.kappa,
                st.n_total)
            assert got.blocks == -(-size // 1024)
            assert got.records == size


@pytest.mark.parametrize("scheme", ["sqrt", "prop"])
def test_hierarchical_draw_matches_dense_distribution(scheme):
    """Fixed-key statistical equivalence vs the dense-CDF path: the
    hierarchical (shard → chunk → record) draw must target exactly the
    dense defensive-mixture p(x), verified by a chi-square over index bins
    against the dense probabilities."""
    from scipy import stats

    rng = np.random.default_rng(17)
    scores = rng.beta(0.2, 1.0, 30_000).astype(np.float32)
    engine = SelectionEngine(np.array_split(scores, 3), num_bins=1024,
                             chunk_records=2048)
    s = 60_000
    idx, _ = engine.draw_sample(jax.random.PRNGKey(0), s, scheme)
    p = _dense_probs(engine, scheme).astype(np.float64)
    bins = 50
    edges = np.linspace(0, engine.n_total, bins + 1).astype(np.int64)
    f_obs = np.histogram(idx, bins=edges)[0]
    mass = np.add.reduceat(p, edges[:-1])
    f_exp = f_obs.sum() * mass / mass.sum()
    assert stats.chisquare(f_obs, f_exp).pvalue > 1e-3


@pytest.mark.parametrize("scheme", ["sqrt", "prop"])
def test_hierarchical_draw_m_p_identity(scheme):
    """Exactness per draw: m(x)·p(x) ≡ 1/n against the dense p(x) — the
    within-chunk weights recomputed at query time reproduce the global
    defensive mixture record-for-record, so reweighting stays unbiased
    with no O(n) state."""
    rng = np.random.default_rng(23)
    scores = rng.random(20_000).astype(np.float32)
    scores[rng.integers(0, 20_000, 700)] = -1.0     # unscored sentinels
    engine = SelectionEngine(np.array_split(scores, 4), num_bins=512,
                             chunk_records=1500)
    idx, m = engine.draw_sample(jax.random.PRNGKey(11), 10_000, scheme)
    p = _dense_probs(engine, scheme).astype(np.float64)
    np.testing.assert_allclose(m.astype(np.float64) * p[idx],
                               1.0 / engine.n_total, rtol=1e-5)


def test_draw_sample_worker_count_invariant():
    """Thread count must never change a single output bit: draws are
    grouped to preassigned slots before the pool runs."""
    rng = np.random.default_rng(29)
    shards = [rng.random(n).astype(np.float32) for n in (7000, 0, 12_000)]
    key = jax.random.PRNGKey(3)
    e1 = SelectionEngine(shards, num_bins=512, chunk_records=1024, workers=1)
    e8 = SelectionEngine(shards, num_bins=512, chunk_records=1024, workers=8)
    for a, b in zip(e1.sketch, e8.sketch):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for scheme in ("sqrt", "prop", "uniform"):
        i1, m1 = e1.draw_sample(key, 5000, scheme)
        i8, m8 = e8.draw_sample(key, 5000, scheme)
        np.testing.assert_array_equal(i1, i8)
        np.testing.assert_array_equal(m1, m8)
    r1 = e1._uniform_in_region(key, 4000, 0.6)
    r8 = e8._uniform_in_region(key, 4000, 0.6)
    np.testing.assert_array_equal(r1, r8)


@pytest.mark.parametrize("qspec", ["rt", "pt", "jt"])
def test_threaded_queries_match_serial(tmp_path, qspec):
    """Full queries through the worker pool return bit-for-bit the serial
    results, through in-memory, memmap-bitmask and callback sinks."""
    ds = make_beta(50_000, 0.02, 1.0, seed=44)
    oracle = array_oracle(ds.labels)
    kw = dict(num_bins=1024, chunk_records=3000)
    serial = SelectionEngine(np.array_split(ds.scores, 4), **kw)
    threaded = SelectionEngine(np.array_split(ds.scores, 4), workers=4, **kw)
    q = {"rt": SUPGQuery(target="recall", gamma=0.9, budget=2000),
         "pt": SUPGQuery(target="precision", gamma=0.8, budget=2000,
                         method="is", two_stage=True),
         "jt": JointSUPGQuery(gamma_recall=0.85, stage_budget=2000)}[qspec]
    key = jax.random.PRNGKey(13)

    def run(engine, sink=None):
        if qspec == "jt":
            return engine.run_joint(key, oracle, q, sink=sink)
        return engine.run(key, oracle, q, sink=sink)

    base = run(serial)
    got = run(threaded)
    assert got.tau == base.tau
    np.testing.assert_array_equal(got.shard_counts, base.shard_counts)
    np.testing.assert_array_equal(np.concatenate(got.masks),
                                  np.concatenate(base.masks))
    bits = BitmaskStore(tmp_path / f"{qspec}.bits")
    np.testing.assert_array_equal(
        np.concatenate(run(threaded, sink=bits).masks),
        np.concatenate(base.masks))
    # callback sink: chunk arrival order is unspecified under the pool,
    # but the rebuilt selection must match exactly
    got_chunks = [[] for _ in threaded.shards]
    run(threaded, sink=CallbackSink(
        lambda sh, gids, folded: got_chunks[sh].append(gids)))
    rebuilt = np.zeros(threaded.n_total, bool)
    for chunks in got_chunks:
        if chunks:
            rebuilt[np.concatenate(chunks)] = True
    np.testing.assert_array_equal(rebuilt, np.concatenate(base.masks))


# -- 1e8-record acceptance: bounded-memory streaming -------------------------

@pytest.mark.slow
def test_1e8_memmap_query_streams_with_bounded_memory(tmp_path):
    """A 1e8-record memmap ScoreStore query completes with peak host
    memory bounded by chunk size: the sketch is built chunk-by-chunk, no
    flat cache or per-record sampling state is allocated, the selection
    lands packed in a memmap BitmaskStore, and no full-corpus boolean mask
    ever exists. Output is verified against the direct threshold baseline
    chunk-by-chunk (counts over the whole corpus, bits over windows)."""
    n = 100_000_000
    chunk = 4_000_000
    store = ScoreStore(tmp_path / "big.scores", n, create=True)
    rng = np.random.default_rng(0)
    for off in range(0, n, chunk):
        store.write(off, rng.random(chunk, dtype=np.float32))

    engine = SelectionEngine([store], num_bins=4096, use_kernel=False,
                             weight_schemes=(), select_backend="ref",
                             chunk_records=chunk)
    # structural bounded-memory guarantees: no O(n) host state beyond the
    # memmap itself
    assert engine._flat is None
    assert not engine._sampling_cache

    def oracle_fn(idx):
        return (store.scores[np.asarray(idx, np.int64)] > 0.9).astype(
            np.float32)

    q = SUPGQuery(target="recall", gamma=0.9, budget=3000, method="uniform")
    sink = BitmaskStore(tmp_path / "big.bits")
    sel = engine.run(jax.random.PRNGKey(1), oracle_fn, q, sink=sink)
    assert 0.0 < sel.tau < 1.0
    assert sel.sink is sink

    # folded positives (below tau) per chunk, for exact count accounting
    pos = sel.sampled_positive_global
    folded = pos[np.asarray(store.scores[pos]) < sel.tau]
    folded_per_chunk = np.bincount(folded // chunk, minlength=n // chunk)

    popcount = np.asarray([bin(i).count("1") for i in range(256)], np.int64)
    arr = sink._arr
    total = 0
    for ci, off in enumerate(range(0, n, chunk)):
        scores_chunk = np.asarray(store.scores[off:off + chunk])
        expect = int(np.count_nonzero(scores_chunk >= sel.tau))
        got = int(popcount[arr[off // 8:(off + chunk) // 8]].sum())
        assert got == expect + int(folded_per_chunk[ci]), (ci, got, expect)
        total += got
    assert sel.total_selected == total
    # windows decoded bit-for-bit against the direct baseline
    for w0 in (0, 48_000_000, n - 80_000):
        w1 = w0 + 80_000
        bits = np.unpackbits(np.asarray(arr[w0 // 8:w1 // 8]),
                             bitorder="little").astype(bool)
        expect = np.asarray(store.scores[w0:w1]) >= sel.tau
        for g in folded[(folded >= w0) & (folded < w1)]:
            expect[g - w0] = True
        np.testing.assert_array_equal(bits, expect)


@pytest.mark.slow
def test_1e8_memmap_is_query_bounded_memory(tmp_path):
    """An importance-weighted (method='is', scheme='sqrt') RT query over a
    1e8-record memmap ScoreStore runs at O(chunk) peak host memory: the
    persistent sampling state is ≤ n / chunk_records entries per
    (shard, scheme) — no per-record CDF or p(x) array ever exists — and the
    query's peak-RSS delta stays far below the ~1.2 GB the dense state
    would allocate. No `weight_schemes=()` escape hatch needed."""
    import resource

    n = 100_000_000
    chunk = 4_000_000
    store = ScoreStore(tmp_path / "big_is.scores", n, create=True)
    rng = np.random.default_rng(2)
    for off in range(0, n, chunk):
        store.write(off, rng.random(chunk, dtype=np.float32))

    engine = SelectionEngine([store], num_bins=4096, use_kernel=False,
                             select_backend="ref", chunk_records=chunk,
                             workers=2)
    assert engine._flat is None
    # persistent hierarchical state: chunk-level only
    assert len(engine._sampling_cache) == 1        # default ("sqrt",) warm
    for states in engine._sampling_cache.values():
        for st in states:
            assert st.cdf.size <= n // chunk
    for cm in engine._chunk_masses:
        assert cm.sizes.size <= n // chunk
        assert int(cm.sizes.sum()) == n

    def oracle_fn(idx):
        return (store.scores[np.asarray(idx, np.int64)] > 0.9).astype(
            np.float32)

    rss0 = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss   # KiB
    q = SUPGQuery(target="recall", gamma=0.9, budget=3000, method="is",
                  weight_scheme="sqrt")
    sink = BitmaskStore(tmp_path / "big_is.bits")
    sel = engine.run(jax.random.PRNGKey(5), oracle_fn, q, sink=sink)
    rss1 = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
    assert 0.0 < sel.tau < 1.0
    # the dense path allocated 12 B/record (~1.2 GB) on first IS draw;
    # the hierarchical draw streams O(chunk) transients only
    assert (rss1 - rss0) * 1024 < 500 * 1024 * 1024, (rss0, rss1)

    # exact count accounting, chunk by chunk, against the direct baseline
    pos = sel.sampled_positive_global
    folded = pos[np.asarray(store.scores[pos]) < sel.tau]
    folded_per_chunk = np.bincount(folded // chunk, minlength=n // chunk)
    popcount = np.asarray([bin(i).count("1") for i in range(256)], np.int64)
    arr = sink._arr
    total = 0
    for ci, off in enumerate(range(0, n, chunk)):
        expect = int(np.count_nonzero(
            np.asarray(store.scores[off:off + chunk]) >= sel.tau))
        got = int(popcount[arr[off // 8:(off + chunk) // 8]].sum())
        assert got == expect + int(folded_per_chunk[ci]), (ci, got, expect)
        total += got
    assert sel.total_selected == total


# -- equivalence: engine vs single-host exact path ---------------------------

def test_engine_consistent_with_run_query():
    """The sharded, sketch-backed engine and the single-host exact path must
    select statistically consistent sets at matched seeds/budgets: both meet
    their target (allowing one delta-level miss across seeds) and the
    selected-set sizes agree within a small factor."""
    ds = make_beta(60_000, 0.01, 1.0, seed=30)
    truth = ds.truth_mask()
    oracle = array_oracle(ds.labels)
    engine = SelectionEngine(np.array_split(ds.scores, 4), num_bins=1024)

    for target, gamma, metric in (
            ("recall", 0.9, queries.recall_of),
            ("precision", 0.8, queries.precision_of)):
        q = SUPGQuery(target=target, gamma=gamma, delta=0.05, budget=3000,
                      method="is")
        misses_engine = misses_exact = 0
        for t in range(3):
            key = jax.random.PRNGKey(100 + t)
            sel = engine.run(key, oracle, q)
            res = queries.run_query(key, ds.scores, oracle, q)
            got_e = metric(np.nonzero(np.concatenate(sel.masks))[0], truth)
            got_x = metric(res.selected, truth)
            misses_engine += got_e < gamma
            misses_exact += got_x < gamma
            n_e = max(sel.total_selected, 1)
            n_x = max(res.selected.shape[0], 1)
            assert 1 / 5 < n_e / n_x < 5, (target, t, n_e, n_x)
        assert misses_engine <= 1, target
        assert misses_exact <= 1, target


# -- QuerySession: async multi-query execution --------------------------------

def _sink_contents(sel):
    """Per-shard sorted selected indices — the sink-contents fingerprint."""
    return [sel.indices(sh) for sh in range(sel.num_shards)]


def test_run_many_session_bit_for_bit_vs_sequential():
    """Acceptance: run_many(concurrency=8) produces identical tau, counts,
    and sink contents to the sequential path (concurrency=1) and to
    independent run/run_joint calls, for an RT/PT/JT mix under one key."""
    ds = make_beta(60_000, 0.02, 1.0, seed=52)
    engine = SelectionEngine(np.array_split(ds.scores, 3), num_bins=1024,
                             chunk_records=7_000)
    oracle = array_oracle(ds.labels)
    batch = [
        SUPGQuery(target="recall", gamma=0.9, budget=2000, method="is"),
        SUPGQuery(target="recall", gamma=0.85, budget=1500, method="noci"),
        SUPGQuery(target="precision", gamma=0.8, budget=2000, method="is",
                  two_stage=True),
        SUPGQuery(target="precision", gamma=0.75, budget=1500,
                  method="uniform"),
        JointSUPGQuery(gamma_recall=0.85, stage_budget=2000),
    ]
    key = jax.random.PRNGKey(33)
    seq = engine.run_many(key, oracle, list(batch), concurrency=1)
    conc = engine.run_many(key, oracle, list(batch), concurrency=8)
    keys = jax.random.split(key, len(batch))
    for k, q, a, b in zip(keys, batch, seq, conc):
        assert a.tau == b.tau
        np.testing.assert_array_equal(a.shard_counts, b.shard_counts)
        for ia, ib in zip(_sink_contents(a), _sink_contents(b)):
            np.testing.assert_array_equal(ia, ib)
        # and both match a fully independent solo execution under the key
        solo = (engine.run_joint(k, oracle, q)
                if isinstance(q, JointSUPGQuery)
                else engine.run(k, oracle, q))
        assert solo.tau == a.tau
        for ia, ib in zip(_sink_contents(solo), _sink_contents(a)):
            np.testing.assert_array_equal(ia, ib)


def test_session_coalesces_oracle_calls_on_overlapping_samples():
    """Acceptance: a session issues fewer underlying oracle invocations
    (batched fn calls) and labels fewer records than the per-query
    sequential baseline when samples overlap, with per-query budgets
    still enforced."""
    ds = make_beta(40_000, 0.02, 1.0, seed=53)
    engine = SelectionEngine(np.array_split(ds.scores, 2), num_bins=1024)
    q = SUPGQuery(target="recall", gamma=0.9, budget=1500, method="is")
    key = jax.random.PRNGKey(9)

    def counting():
        log = []
        arr = np.asarray(ds.labels, np.float32)

        def fn(idx):
            log.append(np.asarray(idx))
            return arr[np.asarray(idx, np.int64)]

        return fn, log

    # sequential baseline: one private channel per query
    fn, log = counting()
    base = [engine.run(key, fn, q) for _ in range(8)]
    base_calls = len(log)
    base_labeled = sum(c.size for c in log)

    # session: same 8 queries (same key => fully overlapping samples)
    fn, log = counting()
    with engine.session(fn) as sess:
        handles = [sess.submit(q, key=key) for _ in range(8)]
        got = [h.result() for h in handles]
    assert len(log) < base_calls                 # coalesced fn batches
    assert sum(c.size for c in log) < base_labeled   # shared-cache reuse
    assert sess.client.fn_calls == len(log)
    for b, g in zip(base, got):
        assert g.tau == b.tau                    # identical results
        np.testing.assert_array_equal(g.shard_counts, b.shard_counts)
        assert g.oracle_calls <= q.budget        # budgets still enforced


def test_session_handles_lifecycle():
    ds = make_beta(20_000, 0.02, 1.0, seed=54)
    engine = SelectionEngine(np.array_split(ds.scores, 2), num_bins=512)
    oracle = array_oracle(ds.labels)
    q = SUPGQuery(target="recall", gamma=0.9, budget=800)
    with engine.session(oracle, concurrency=2) as sess:
        hs = [sess.submit(q, key=jax.random.PRNGKey(i)) for i in range(4)]
        assert not any(h.done for h in hs)
        first = hs[0].result()                   # pumps until hs[0] is done
        assert hs[0].done and first.total_selected > 0
    # context exit pumps the rest to completion
    assert all(h.done for h in hs)
    assert all(h.result().total_selected > 0 for h in hs)
    with pytest.raises(RuntimeError, match="closed"):
        sess.submit(q)
    # abandoned sessions reject unfinished queries instead of hanging
    sess2 = engine.session(oracle)
    h2 = sess2.submit(q)
    sess2.close(abandon=True)
    with pytest.raises(RuntimeError, match="abandoned"):
        h2.result()


def test_session_shared_client_across_sessions():
    """An explicit BatchingOracle passes through the adapter, so its label
    cache carries across sessions and run_many batches."""
    from repro.core.oracle import BatchingOracle

    ds = make_beta(20_000, 0.02, 1.0, seed=55)
    engine = SelectionEngine(np.array_split(ds.scores, 2), num_bins=512)
    client = BatchingOracle(array_oracle(ds.labels))
    q = SUPGQuery(target="recall", gamma=0.9, budget=800)
    key = jax.random.PRNGKey(4)
    a = engine.run(key, client, q)
    calls_after_first = client.fn_calls
    b = engine.run(key, client, q)               # same sample: all cached
    assert client.fn_calls == calls_after_first
    assert b.tau == a.tau and b.oracle_calls == 0


def test_run_many_validates_sinks_before_keys():
    """Regression: the sink-list length check must fire before any key
    handling, and sharing one sink object across queries is rejected."""
    ds = make_beta(5_000, 0.05, 1.0, seed=56)
    engine = SelectionEngine([ds.scores], num_bins=512)
    oracle = array_oracle(ds.labels)
    qs = [SUPGQuery(target="recall", gamma=0.9, budget=200)] * 2
    with pytest.raises(ValueError, match="one sink"):
        # key=None used to be split before the validation could fire
        engine.run_many(None, oracle, qs, sinks=[None])
    shared = IndexSink()
    with pytest.raises(ValueError, match="shared"):
        engine.run_many(None, oracle, qs, sinks=[shared, shared])
    assert engine.run_many(None, oracle, [], sinks=[]) == []


def test_sink_refuses_double_open():
    sink = IndexSink()
    sink.open([10, 5])
    with pytest.raises(RuntimeError, match="already open"):
        sink.open([10, 5])
    sink.close()
    sink.open([4])                               # sequential reuse is fine
    sink.emit(0, np.asarray([1, 2]))
    sink.close()
    np.testing.assert_array_equal(sink.indices(0), [1, 2])


def test_session_drain_failure_fails_loud_not_silent():
    """Regression: a drain that blows up mid-session (broken oracle) used
    to leave in-flight plans with stale inboxes — the next pump resumed
    them with the previous round's payload and returned silently corrupted
    selections. Every affected handle must now raise, and the session must
    stay pumpable (close() terminates cleanly)."""
    ds = make_beta(10_000, 0.05, 1.0, seed=57)
    engine = SelectionEngine(np.array_split(ds.scores, 2), num_bins=512)
    q = SUPGQuery(target="recall", gamma=0.9, budget=500)
    boom = [True]
    arr = np.asarray(ds.labels, np.float32)

    def flaky(idx):
        if boom[0]:
            raise IOError("labeling backend down")
        return arr[np.asarray(idx, np.int64)]

    sess = engine.session(flaky, concurrency=4)
    hs = [sess.submit(q, key=jax.random.PRNGKey(i)) for i in range(3)]
    with pytest.raises(IOError, match="backend down"):
        hs[0].result()
    boom[0] = False                       # backend recovers...
    for h in hs:                          # ...but the round was poisoned:
        with pytest.raises(IOError):      # affected plans fail loud, never
            h.result()                    # resume on stale labels
    sess.close()                          # and the session winds down clean
    fresh = engine.session(flaky)
    ok = fresh.submit(q, key=jax.random.PRNGKey(0)).result()
    assert ok.total_selected > 0
    fresh.close()


def test_failed_query_releases_sink_for_reuse():
    """Regression: a JT plan that dies mid-verification (or an emission
    pass whose consumer raises) must release its sink — the double-open
    guard would otherwise wedge the sink object forever."""
    ds = make_beta(10_000, 0.05, 1.0, seed=58)
    engine = SelectionEngine(np.array_split(ds.scores, 2), num_bins=512)
    arr = np.asarray(ds.labels, np.float32)
    calls = [0]

    def flaky(idx):
        calls[0] += 1
        if calls[0] > 1:                    # RT stage ok, verification dies
            raise IOError("down")
        return arr[np.asarray(idx, np.int64)]

    sink = IndexSink()
    jt = JointSUPGQuery(gamma_recall=0.8, stage_budget=400)
    with pytest.raises(IOError):
        engine.run_joint(jax.random.PRNGKey(1), flaky, jt, sink=sink,
                         chunk_records=500)
    # the sink is reusable: the same object serves the retry
    sel = engine.run_joint(jax.random.PRNGKey(1), array_oracle(ds.labels),
                           jt, sink=sink, chunk_records=500)
    assert sel.total_selected > 0 and sel.sink is sink


def test_session_submit_time_drain_failure_fails_loud():
    """Regression: with max_batch set, client.submit() inside a scheduler
    round can auto-drain and blow up *before* the round state was
    committed; stale inboxes then resumed plans on the previous round's
    labels. Every affected handle must raise instead."""
    ds = make_beta(10_000, 0.05, 1.0, seed=59)
    engine = SelectionEngine(np.array_split(ds.scores, 2), num_bins=512)
    q = SUPGQuery(target="recall", gamma=0.9, budget=400)
    boom = [True]
    arr = np.asarray(ds.labels, np.float32)

    def flaky(idx):
        if boom[0]:
            raise IOError("backend down")
        return arr[np.asarray(idx, np.int64)]

    # max_batch far below the per-query sample size => the first submit
    # crosses the threshold and auto-drains inside the round
    sess = engine.session(flaky, concurrency=4, max_batch=64)
    hs = [sess.submit(q, key=jax.random.PRNGKey(i)) for i in range(3)]
    with pytest.raises(IOError, match="backend down"):
        hs[0].result()
    boom[0] = False
    for h in hs:
        with pytest.raises(IOError):        # loud, never stale-label resumes
            h.result()
    sess.close()
    # the engine itself is unharmed
    ok = engine.run(jax.random.PRNGKey(0), array_oracle(ds.labels), q)
    assert ok.total_selected > 0


# -- PR 6: overlapped rounds, worker clamp, overlap stats ---------------------

def test_session_overlapped_drains_bit_for_bit_across_workers():
    """Acceptance: the double-buffered scheduler (drains overlapping the
    other cohort's compute) is bit-for-bit equal to the sequential path
    for an RT/PT/JT mix at workers in {1, 4, 8}. clamp_workers=False so
    the requested counts are honored even on small CI boxes."""
    ds = make_beta(30_000, 0.02, 1.0, seed=57)
    shards = np.array_split(ds.scores, 3)
    oracle = array_oracle(ds.labels)
    batch = [
        SUPGQuery(target="recall", gamma=0.9, budget=1200, method="is"),
        SUPGQuery(target="precision", gamma=0.8, budget=1200, method="is",
                  two_stage=True),
        SUPGQuery(target="recall", gamma=0.85, budget=1000, method="noci"),
        JointSUPGQuery(gamma_recall=0.85, stage_budget=1200),
    ]
    key = jax.random.PRNGKey(77)
    ref = SelectionEngine(shards, num_bins=1024, chunk_records=5_000,
                          workers=1).run_many(key, oracle, list(batch),
                                              concurrency=1)
    for w in (1, 4, 8):
        with SelectionEngine(shards, num_bins=1024, chunk_records=5_000,
                             workers=w, clamp_workers=False) as engine:
            got = engine.run_many(key, oracle, list(batch), concurrency=8)
        for a, b in zip(ref, got):
            assert a.tau == b.tau, w
            np.testing.assert_array_equal(a.shard_counts, b.shard_counts)
            for ia, ib in zip(_sink_contents(a), _sink_contents(b)):
                np.testing.assert_array_equal(ia, ib)


def test_engine_worker_clamp_logs_once_with_escape_hatch(monkeypatch,
                                                         caplog):
    """Oversubscription fix: requested workers are clamped to cpu_count
    (logged exactly once process-wide); clamp_workers=False keeps the
    requested count for determinism tests."""
    import logging

    from repro.core import engine as engine_mod

    monkeypatch.setattr(engine_mod.os, "cpu_count", lambda: 2)
    monkeypatch.setattr(engine_mod, "_clamp_logged", False)
    scores = np.linspace(0.0, 1.0, 1_000, dtype=np.float32)
    with caplog.at_level(logging.INFO, logger="repro.core.engine"):
        with SelectionEngine([scores], num_bins=64, workers=8) as e1:
            assert e1.workers == 2
            with SelectionEngine([scores], num_bins=64, workers=8) as e2:
                assert e2.workers == 2
    clamps = [r for r in caplog.records if "clamping" in r.getMessage()]
    assert len(clamps) == 1                 # logged once, not per engine
    with SelectionEngine([scores], num_bins=64, workers=8,
                         clamp_workers=False) as e3:
        assert e3.workers == 8              # escape hatch honored


def test_session_stats_record_overlap_and_fusion():
    """SessionStats from a batch of same-shape queries: rounds/drains are
    counted, drain timers are sane, and the emission walks of co-resident
    queries fused into shared chunk passes (spans_saved > 0)."""
    ds = make_beta(30_000, 0.02, 1.0, seed=58)
    engine = SelectionEngine(np.array_split(ds.scores, 2), num_bins=1024,
                             chunk_records=4_000)
    oracle = array_oracle(ds.labels)
    qs = [SUPGQuery(target="recall", gamma=0.9, budget=1000, method="is")
          for _ in range(4)]
    keys = jax.random.split(jax.random.PRNGKey(5), len(qs))
    with engine.session(oracle) as sess:
        handles = [sess.submit(q, key=k) for q, k in zip(qs, keys)]
        results = [h.result() for h in handles]
    assert all(r.total_selected > 0 for r in results)
    st = sess.stats
    assert st.rounds > 0
    assert st.plan_steps >= len(qs)         # every plan stepped >= once
    assert st.drains >= 1                   # labeling went through drains
    assert st.drain_busy_s >= st.drain_wait_s >= 0.0
    assert st.overlap_hidden_s >= 0.0
    # all four RT emission walks ran through the fusion path, and walks
    # sharing a round+geometry collapsed into shared spans
    assert st.fused_walks == len(qs)
    assert st.walk_spans >= st.fused_spans > 0
    assert st.spans_saved > 0
