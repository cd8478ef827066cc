"""Live corpus plane: incremental-ingestion equivalence, epoch pinning,
standing-query re-emission, the drift sentinel, and the serve surface."""
import time

import numpy as np
import pytest

import jax
from hypothesis import given, settings, strategies as st

from repro.core import binned, sampling
from repro.core.engine import SelectionEngine
from repro.core.oracle import array_oracle
from repro.core.queries import JointSUPGQuery, SUPGQuery
from repro.data.pipeline import CallbackSink
from repro.data.synthetic import make_beta, make_drift_pair
from repro.live import DriftSentinel, IngestPlane, StandingRegistry
from repro.serve.server import SelectionServer

N_SHARDS, SHARD = 6, 20_000

QUERIES = [
    SUPGQuery(target="recall", gamma=0.9, budget=2000, method="is"),
    SUPGQuery(target="precision", gamma=0.9, budget=2000, method="is"),
    JointSUPGQuery(gamma_recall=0.85, stage_budget=2000),
]

ENGINE_KW = dict(num_bins=1024, use_kernel=False, chunk_records=1 << 13)


@pytest.fixture(scope="module")
def corpus():
    ds = make_beta(N_SHARDS * SHARD, 0.05, 1.0, seed=3)
    shards = [ds.scores[i * SHARD:(i + 1) * SHARD]
              for i in range(N_SHARDS)]
    return ds, shards


def _assert_same(a, b):
    """Bit-for-bit selection equality: tau, counts, per-shard masks."""
    assert float(a.tau) == float(b.tau)
    assert a.total_selected == b.total_selected
    assert len(a.masks) == len(b.masks)
    for ma, mb in zip(a.masks, b.masks):
        np.testing.assert_array_equal(ma, mb)
    np.testing.assert_array_equal(a.sampled_positive_global,
                                  b.sampled_positive_global)


# -- incremental ingestion == cold build ------------------------------------

@pytest.mark.parametrize("workers", [1, 4, 8])
def test_incremental_append_matches_cold_build(corpus, workers):
    """The acceptance bar: build over S1..S3, append S4..S6 (one single
    then one batch append), and every RT/PT/JT result — tau, counts,
    masks, sampled positives — is bit-for-bit the cold build's."""
    ds, shards = corpus
    oracle = array_oracle(ds.labels)
    key = jax.random.PRNGKey(42)
    with SelectionEngine(shards, workers=workers, **ENGINE_KW) as cold:
        want = cold.run_many(key, oracle, QUERIES)
    with SelectionEngine(shards[:3], workers=workers, **ENGINE_KW) as warm:
        plane = IngestPlane(warm)
        assert plane.append(shards[3]) == 1
        assert plane.append([shards[4], shards[5]]) == 2
        assert warm.epoch == 2
        assert warm.n_total == N_SHARDS * SHARD
        assert plane.shards_since(0) == [3, 4, 5]
        assert plane.shards_since(1) == [4, 5]
        got = warm.run_many(key, oracle, QUERIES)
    for a, b in zip(want, got):
        _assert_same(a, b)


def test_incremental_append_matches_cold_via_server(corpus):
    """Same equivalence through the serving plane: `SelectionServer`
    hosting an appended-to engine answers like a cold build."""
    ds, shards = corpus
    key = jax.random.PRNGKey(7)
    keys = jax.random.split(key, len(QUERIES))
    with SelectionEngine(shards, workers=2, **ENGINE_KW) as cold:
        want = cold.run_many(key, array_oracle(ds.labels), QUERIES)
    eng = SelectionEngine(shards[:3], workers=2, **ENGINE_KW)
    with SelectionServer(eng, array_oracle(ds.labels)) as srv:
        assert srv.append(shards[3]) == 1
        assert srv.append(shards[4:]) == 2
        handles = [srv.submit(q, key=k) for q, k in zip(QUERIES, keys)]
        got = [h.result(timeout=300) for h in handles]
    for a, b in zip(want, got):
        _assert_same(a, b)


def test_inflight_plan_pins_epoch_across_append(corpus):
    """A partially-stepped plan keeps its pinned epoch: an append landing
    mid-query must not change the result (or the mask shard count)."""
    ds, shards = corpus
    oracle = array_oracle(ds.labels)
    q = QUERIES[0]
    key = jax.random.PRNGKey(5)
    with SelectionEngine(shards[:3], **ENGINE_KW) as ref:
        want = ref.run(key, oracle, q)
    with SelectionEngine(shards[:3], **ENGINE_KW) as eng:
        with eng.session(oracle) as sess:
            h = sess.submit(q, key=key)
            sess.step()                      # plan started, epoch pinned
            assert IngestPlane(eng).append(shards[3]) == 1
            sel = h.result()
        assert len(sel.masks) == 3           # the pinned epoch's shards
        _assert_same(want, sel)


def test_append_rejects_unknown_epoch(corpus):
    _, shards = corpus
    with SelectionEngine(shards[:1], **ENGINE_KW) as eng:
        plane = IngestPlane(eng)
        with pytest.raises(ValueError, match="not recorded"):
            plane.shards_since(7)


# -- standing queries -------------------------------------------------------

def test_standing_query_reemits_exact_threshold_set(corpus):
    """After an append, one catch-up walk streams exactly {A >= tau} over
    the appended shards (and only those) into the standing sink."""
    ds, shards = corpus
    oracle = array_oracle(ds.labels)
    got = []
    sink = CallbackSink(
        lambda sid, idx, folded: got.append((sid, np.asarray(idx).copy())))
    with SelectionEngine(shards[:4], **ENGINE_KW) as eng:
        with eng.session(oracle) as sess:
            reg = StandingRegistry(IngestPlane(eng), sess)
            sq = reg.register(QUERIES[0], key=jax.random.PRNGKey(11),
                              sink=sink)
            reg.settle()
            tau = sq.wait_certified(timeout=0)
            got.clear()                       # keep only re-emissions
            reg.plane.append([shards[4], shards[5]])
            assert reg.pump() == 1            # both shards, one walk
            reg.settle()
            assert (sq.emissions, sq.epoch, sq.reemit_failures) == (1, 1, 0)
            assert reg.pump() == 0            # caught up: nothing to do
    assert got and all(sid >= 4 for sid, _ in got)
    emitted = np.sort(np.concatenate([idx for _, idx in got]))
    want = np.sort(np.concatenate(
        [j * SHARD + np.flatnonzero(shards[j] >= np.float32(tau))
         for j in (4, 5)]))
    np.testing.assert_array_equal(emitted, want)
    assert sq.records_reemitted == want.size


# -- drift sentinel ---------------------------------------------------------

def test_sentinel_triggers_on_drift_and_stays_quiet_on_control():
    """Table 3's drift scenario: appending the shifted Beta(0.01, 2) half
    trips the sentinel and auto re-validates; appending a fresh
    same-distribution sample does not."""
    train, shifted = make_drift_pair(n=200_000, seed=0)
    control = make_beta(200_000, 0.01, 1.0, seed=99)
    q = SUPGQuery(target="recall", gamma=0.9, budget=4000, method="is")

    def run(appended):
        labels = np.concatenate([train.labels, appended.labels])
        shards = [np.ascontiguousarray(a)
                  for a in np.array_split(train.scores, 4)]
        with SelectionEngine(shards, num_bins=1024,
                             use_kernel=False) as eng:
            sent = DriftSentinel(eng, array_oracle(labels),
                                 probe_budget=4096, sigma=4.0)
            watch = sent.watch(q, key=jax.random.PRNGKey(0))
            tau0 = watch.tau
            IngestPlane(eng).append(appended.scores)
            rep = sent.audit(watch, key=jax.random.PRNGKey(1))
            return sent, watch, tau0, rep

    sent, watch, tau0, rep = run(shifted)
    assert rep.drifted and rep.revalidated and rep.epoch == 1
    assert rep.tau_before == tau0 and watch.tau == rep.tau_after
    assert watch.epoch == 1                  # re-baselined on the new epoch
    assert rep.revalidation_spent > 0
    assert (sent.checks, sent.triggers, sent.revalidations) == (1, 1, 1)

    sent, watch, tau0, rep = run(control)
    assert not rep.drifted and not rep.revalidated
    assert watch.tau == tau0                 # nothing re-validated
    assert (sent.checks, sent.triggers, sent.revalidations) == (1, 0, 0)


# -- serve surface ----------------------------------------------------------

def test_server_live_surface_counters(corpus):
    """subscribe(audit=True) + append: the scheduler certifies, audits the
    new epoch, re-emits the catch-up walk, and the stats snapshot carries
    the live counters."""
    ds, shards = corpus
    eng = SelectionEngine(shards[:4], **ENGINE_KW)
    got = []
    sink = CallbackSink(
        lambda sid, idx, folded: got.append((sid, np.asarray(idx).copy())))
    with SelectionServer(eng, array_oracle(ds.labels),
                         sentinel_probe_budget=512) as srv:
        sq = srv.subscribe(QUERIES[0], key=jax.random.PRNGKey(1),
                           sink=sink, audit=True)
        tau = sq.wait_certified(timeout=300)
        assert tau == pytest.approx(sq.tau)
        n_certified = len(got)               # certification walk output
        assert srv.append(shards[4]) == 1
        deadline = time.monotonic() + 300
        while sq.emissions < 1 and time.monotonic() < deadline:
            time.sleep(0.01)
        assert sq.emissions == 1 and sq.epoch == 1
        assert sq.last_error is None
        stats = srv.stats()
    assert stats.epochs == 1
    assert stats.records_ingested == SHARD
    assert stats.standing_queries == 1
    assert stats.standing_emissions == 1
    assert stats.sentinel_checks >= 1
    assert "live:" in stats.format()
    assert all(sid == 4 for sid, _ in got[n_certified:])


def test_server_append_and_subscribe_refused_after_close(corpus):
    ds, shards = corpus
    srv = SelectionServer(SelectionEngine(shards[:1], **ENGINE_KW),
                          array_oracle(ds.labels))
    srv.close()
    with pytest.raises(Exception, match="closed"):
        srv.append(shards[1])
    with pytest.raises(Exception, match="closed"):
        srv.subscribe(QUERIES[0])


# -- merge/fold properties (satellite: split-corpus bitwise invariants) -----

@settings(max_examples=15, deadline=None)
@given(st.integers(0, 2**31 - 1), st.integers(1, 400))
def test_chunk_sketch_fold_split_invariance(seed, chunk):
    """Folding a prefix of per-chunk sketches, then merging the rest on
    top, is bit-for-bit the full left fold — counts, sum_w, sum_a, and
    both weight schemes' raw masses. This is the exact operation
    `_append_shards` performs on the global sketch, so it is the whole
    incremental-ingestion bitwise story in one invariant; it holds for
    tile-aligned and ragged chunk sizes alike."""
    rng = np.random.default_rng(seed)
    n = int(rng.integers(1, 3000))
    scores = rng.random(n).astype(np.float32)
    parts = [binned.chunk_sketch_stats(scores[i:i + chunk], 64,
                                       use_kernel=False)
             for i in range(0, n, chunk)]
    sketches = [p[0] for p in parts]
    full = binned.merge_sketches(*sketches)
    for k in {1, len(sketches) // 2, len(sketches)}:
        prefix = binned.merge_sketches(*sketches[:k])
        refold = binned.merge_sketches(prefix, *sketches[k:])
        np.testing.assert_array_equal(np.asarray(full.counts),
                                      np.asarray(refold.counts))
        np.testing.assert_array_equal(np.asarray(full.sum_w),
                                      np.asarray(refold.sum_w))
        np.testing.assert_array_equal(np.asarray(full.sum_a),
                                      np.asarray(refold.sum_a))
        # raw sampling masses (sqrt and a schemes) fold the same way
        for j in (1, 2):
            masses = np.asarray([p[j] for p in parts], np.float64)
            whole = sampling.append_cdf(np.empty(0, np.float64), masses)
            grown = sampling.append_cdf(
                sampling.append_cdf(np.empty(0, np.float64), masses[:k]),
                masses[k:])
            np.testing.assert_array_equal(whole, grown)


@settings(max_examples=25)
@given(st.lists(st.floats(0.0, 10.0), min_size=1, max_size=50),
       st.integers(0, 50))
def test_append_cdf_continues_cold_cumsum_bitwise(masses, split):
    """`append_cdf` over a split mass list equals the cold cumsum over
    the whole list, element-for-element bitwise."""
    m = np.asarray(masses, np.float64)
    k = min(split, m.size)
    cold = sampling.append_cdf(np.empty(0, np.float64), m)
    grown = sampling.append_cdf(
        sampling.append_cdf(np.empty(0, np.float64), m[:k]), m[k:])
    np.testing.assert_array_equal(cold, grown)
