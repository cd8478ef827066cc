"""Durability plane: journal framing, atomic commits, crashpoint
acceptance (kill -> restore -> resume == the uncrashed run, bit for bit),
and epoch GC."""
import os
import time

import numpy as np
import pytest

import jax

from repro.core.engine import SelectionEngine
from repro.core.queries import JointSUPGQuery, SUPGQuery
from repro.data.pipeline import BitmaskStore, ScoreStore
from repro.durable import atomic
from repro.durable.journal import EpochJournal, scan
from repro.durable.recovery import DurabilityPlane
from repro.live.ingest import IngestPlane
from repro.serve.server import SelectionServer
from repro.testing import CrashInjector, SimulatedCrash, crash_schedule

BASE_N, DELTA_N = 2048, 1024
ENGINE_KW = dict(num_bins=64, use_kernel=False)

QUERIES = [
    SUPGQuery(target="recall", gamma=0.9, budget=192, method="is"),
    SUPGQuery(target="precision", gamma=0.9, budget=192, method="is"),
    JointSUPGQuery(gamma_recall=0.85, stage_budget=192),
]

# Crashpoints on the ingest/append/standing-catch-up path (the snapshot
# path's `pre_snapshot_publish` is exercised separately).
APPEND_PATH_POINTS = [
    "pre_fsync", "pre_rename", "journal_pre_append", "journal_pre_fsync",
    "post_journal_pre_install", "mid_bitmask_commit",
]


def _base_shards():
    return [np.linspace(0.0, 1.0, BASE_N, dtype=np.float32)]


def _deltas():
    rng = np.random.default_rng(11)
    return [rng.beta(0.05, 1.0, DELTA_N).astype(np.float32)
            for _ in range(3)]


def _oracle(idx):
    return (np.asarray(idx) % 7 == 0).astype(np.float32)


# ---------------------------------------------------------------------------
# journal framing
# ---------------------------------------------------------------------------

def test_journal_truncation_property(tmp_path):
    """Truncating the file at *every* byte offset: replay never raises
    and never invents a record — it returns a strict prefix."""
    path = str(tmp_path / "j.log")
    records = [{"type": "append", "epoch": e, "shards": []}
               for e in (1, 2, 3)]
    with EpochJournal(path) as j:
        for r in records:
            j.append(r)
    data = open(path, "rb").read()
    cut = str(tmp_path / "cut.log")
    prefix_lens = []
    for n in range(len(data) + 1):
        with open(cut, "wb") as f:
            f.write(data[:n])
        got, valid = scan(cut)
        assert valid <= n
        assert got == records[:len(got)]        # prefix, never invented
        prefix_lens.append(len(got))
    assert prefix_lens[0] == 0 and prefix_lens[-1] == 3
    assert prefix_lens == sorted(prefix_lens)   # monotone in bytes kept


def test_journal_corrupt_frame_stops_scan(tmp_path):
    path = str(tmp_path / "j.log")
    with EpochJournal(path) as j:
        j.append({"epoch": 1})
        j.append({"epoch": 2})
    data = bytearray(open(path, "rb").read())
    first_len = scan(path)[1] // 2  # two equal frames
    data[first_len + 14] ^= 0xFF    # corrupt the second frame's payload
    open(path, "wb").write(bytes(data))
    got, valid = scan(path)
    assert [r["epoch"] for r in got] == [1]
    assert valid == first_len


def test_journal_reopen_truncates_torn_tail_and_appends(tmp_path):
    path = str(tmp_path / "j.log")
    with EpochJournal(path) as j:
        j.append({"epoch": 1})
    with open(path, "ab") as f:
        f.write(b"EPJ1\x07\x00")    # half a header
    with EpochJournal(path) as j:
        assert [r["epoch"] for r in j.records] == [1]
        j.append({"epoch": 2})
    assert [r["epoch"] for r in EpochJournal(path).replay()] == [1, 2]


# ---------------------------------------------------------------------------
# crash injector + atomic replace
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("point", ["pre_fsync", "pre_rename"])
def test_atomic_replace_crash_leaves_old_file(tmp_path, point):
    path = str(tmp_path / "s.json")
    atomic.atomic_write_json(path, {"v": 1})
    with CrashInjector({point: 0}):
        with pytest.raises(SimulatedCrash):
            atomic.atomic_write_json(path, {"v": 2})
    assert atomic.read_json(path) == {"v": 1}
    atomic.atomic_write_json(path, {"v": 3})    # hook uninstalled
    assert atomic.read_json(path) == {"v": 3}


def test_crash_injector_latches(tmp_path):
    """After firing once, every later crashpoint raises too — a dead
    process cannot keep committing."""
    inj = CrashInjector({"pre_rename": 0})
    with inj:
        with pytest.raises(SimulatedCrash):
            atomic.atomic_write_json(str(tmp_path / "a.json"), {})
        with pytest.raises(SimulatedCrash):
            atomic.crashpoint("journal_pre_append")    # unscheduled point
    assert inj.fired and inj.fired_at == "pre_rename"


def test_crash_injector_rejects_unknown_points():
    with pytest.raises(ValueError, match="unknown crashpoint"):
        CrashInjector({"not_a_point": 0})


def test_crash_schedule_deterministic():
    assert crash_schedule(42) == crash_schedule(42)
    (point, hit), = crash_schedule(42).items()
    assert point in atomic.CRASHPOINTS and 0 <= hit < 3


# ---------------------------------------------------------------------------
# two-phase store commits
# ---------------------------------------------------------------------------

def test_score_store_append_two_phase(tmp_path):
    path = str(tmp_path / "s.scores")
    store = ScoreStore(path, 8, create=True)
    store.write(0, np.arange(8, dtype=np.float32))
    with CrashInjector({"pre_length_commit": 0}):
        with pytest.raises(SimulatedCrash):
            store.append(np.full(4, 9.0, np.float32))
    # The crashed grow was never acknowledged: reopening recovers to the
    # committed length, and re-issuing the append is exactly-once.
    again = ScoreStore(path, 1 << 20)     # over-ask: clamped to committed
    assert len(again) == 8
    assert again.append(np.full(4, 9.0, np.float32)) == 12
    assert np.array_equal(again.read(8), np.full(4, 9.0, np.float32))
    reopened = ScoreStore(path, 1 << 20)
    assert len(reopened) == 12


def test_bitmask_grow_preserves_committed_bits(tmp_path):
    path = str(tmp_path / "sel.bits")
    store = BitmaskStore(path)
    store.open([100, 37])
    store.emit(0, np.asarray([1, 3, 99]))
    store.emit(1, np.asarray([0, 36]))
    store.close()
    before0, before1 = store.mask(0).copy(), store.mask(1).copy()

    # A crash mid-grow commits nothing: the old layout stays current.
    grower = BitmaskStore(path)
    with CrashInjector({"mid_bitmask_commit": 0}):
        with pytest.raises(SimulatedCrash):
            grower.open([100, 37, 64])
    meta = atomic.read_json(path + ".meta.json")
    assert meta["shard_sizes"] == [100, 37]

    # Re-growing after the crash preserves every committed bit.
    grown = BitmaskStore(path)
    grown.open([100, 37, 64])
    grown.emit(2, np.asarray([5]))
    grown.close()
    assert np.array_equal(grown.mask(0), before0)
    assert np.array_equal(grown.mask(1), before1)
    assert grown.indices(2).tolist() == [5]


def test_bitmask_incompatible_layout_starts_fresh(tmp_path):
    path = str(tmp_path / "sel.bits")
    store = BitmaskStore(path)
    store.open([16])
    store.emit(0, np.asarray([0, 1]))
    store.close()
    fresh = BitmaskStore(path)
    fresh.open([32])          # shard 0 resized: not an extension
    fresh.close()
    assert fresh.indices(0).size == 0


# ---------------------------------------------------------------------------
# epoch GC
# ---------------------------------------------------------------------------

def test_epoch_gc_respects_pins():
    with SelectionEngine(_base_shards(), **ENGINE_KW) as eng:
        plane = IngestPlane(eng)
        pinned = eng.pin()                      # pin epoch 0
        for d in _deltas():
            plane.append(d)
        assert eng.epochs_live == 4             # current + 3 superseded
        assert eng.gc_epochs() == 2             # epoch 0 is pinned
        assert eng.epochs_live == 2
        assert pinned.shards                    # untouched while pinned
        eng.unpin(pinned)
        assert eng.gc_epochs() == 1
        assert eng.epochs_freed == 3
        assert eng.epochs_live == 1
        with pytest.raises(ValueError, match="no live pins"):
            eng.unpin(pinned)


def test_plans_unpin_their_epoch():
    with SelectionEngine(_base_shards(), **ENGINE_KW) as eng:
        eng.run(jax.random.PRNGKey(0), _oracle, QUERIES[0])
        IngestPlane(eng).append(_deltas()[0])
        assert eng.gc_epochs() == 1             # nothing left pinned


# ---------------------------------------------------------------------------
# replay: idempotence + engine-level bit-for-bit recovery
# ---------------------------------------------------------------------------

def test_replay_is_idempotent(tmp_path):
    dur = DurabilityPlane(str(tmp_path / "dur"))
    with SelectionEngine(_base_shards(), **ENGINE_KW) as eng:
        plane = IngestPlane(eng)
        for d in _deltas():
            arrs = dur.record_append(d, epoch=plane.epoch + 1)
            plane.append(arrs)
        assert dur.replay_into(plane) == 0      # already applied: no-op
    with SelectionEngine(_base_shards(), **ENGINE_KW) as eng2:
        plane2 = IngestPlane(eng2)
        assert dur.replay_into(plane2) == 3
        assert dur.replay_into(plane2) == 0     # replaying again: no-op
        assert eng2.n_total == BASE_N + 3 * DELTA_N


@pytest.mark.parametrize("workers", [1, 4, 8])
def test_run_many_crash_restore_bit_for_bit(tmp_path, workers):
    """Kill mid-append, rebuild from the journal, run RT/PT/JT through
    `run_many`: results equal the never-crashed engine's bit for bit."""
    kw = dict(ENGINE_KW, workers=workers)
    deltas = _deltas()
    key = jax.random.PRNGKey(5)

    with SelectionEngine(_base_shards(), **kw) as ref_eng:
        ref_plane = IngestPlane(ref_eng)
        for d in deltas:
            ref_plane.append(d)
        ref = ref_eng.run_many(key, _oracle, QUERIES)

    dur = DurabilityPlane(str(tmp_path / "dur"))
    with SelectionEngine(_base_shards(), **kw) as eng:
        plane = IngestPlane(eng)
        with CrashInjector({"post_journal_pre_install": 2}):
            with pytest.raises(SimulatedCrash):
                for d in deltas:
                    plane.append(dur.record_append(d, epoch=plane.epoch + 1))

    with SelectionEngine(_base_shards(), **kw) as rec_eng:
        rec_plane = IngestPlane(rec_eng)
        # The journaled-but-uninstalled epoch replays too: the append was
        # acknowledged to the journal, so recovery lands on the timeline
        # the caller was about to see.
        assert dur.replay_into(rec_plane) == 3
        got = rec_eng.run_many(key, _oracle, QUERIES)

    for r, g in zip(ref, got):
        assert g.tau == r.tau
        assert g.oracle_calls == r.oracle_calls
        assert np.array_equal(g.shard_counts, r.shard_counts)
        for sh in range(len(r.shard_sizes)):
            assert np.array_equal(g.indices(sh), r.indices(sh))


# ---------------------------------------------------------------------------
# server crashpoint acceptance
# ---------------------------------------------------------------------------

def _make_server(root, workers, sink_dir, tag):
    eng = SelectionEngine(_base_shards(), workers=workers, **ENGINE_KW)
    srv = SelectionServer(eng, _oracle, durable=root,
                          quotas={"t": 1_000_000})
    sqs = [srv.subscribe(q, tenant="t", key=jax.random.PRNGKey(j),
                         sink=BitmaskStore(
                             os.path.join(sink_dir, f"{tag}_{j}.bits")))
           for j, q in enumerate(QUERIES)]
    for sq in sqs:
        sq.wait_certified(timeout=120)
    srv.snapshot()
    return srv, sqs


def _wait_quiescent(srv, sqs, epoch, inj=None, timeout=120):
    deadline = time.monotonic() + timeout
    while True:
        if inj is not None and inj.fired:
            return False
        if all(sq.epoch >= epoch and not sq._busy for sq in sqs) \
                and not srv._registry.has_pending():
            return True
        if srv._fatal is not None:
            raise AssertionError(f"scheduler died: {srv._fatal!r}")
        assert time.monotonic() < deadline, "standing catch-up stalled"
        time.sleep(0.01)


def _collect(srv, sqs):
    n_shards = len(srv.engine.shards)
    taus = [sq.tau for sq in sqs]
    masks = [[sq.sink.mask(sh).copy() for sh in range(n_shards)]
             for sq in sqs]
    charged = srv.stats().tenants["t"].oracle_charged
    return taus, masks, charged


@pytest.fixture(scope="module")
def uncrashed_reference(tmp_path_factory):
    """tau / sink-bits / ledger of the never-crashed run, per worker count
    (computed lazily, cached for every crashpoint case)."""
    cache = {}

    def get(workers):
        if workers not in cache:
            d = str(tmp_path_factory.mktemp(f"ref_w{workers}"))
            srv, sqs = _make_server(os.path.join(d, "dur"), workers, d,
                                    "ref")
            for i, delta in enumerate(_deltas()):
                srv.append(delta)
                _wait_quiescent(srv, sqs, i + 1)
            cache[workers] = _collect(srv, sqs)
            srv.close()
        return cache[workers]

    return get


def _crash_restore_resume(tmp_path, workers, point, hit, reference):
    ref_taus, ref_masks, ref_charged = reference
    root = str(tmp_path / "dur")
    deltas = _deltas()
    srv, sqs = _make_server(root, workers, str(tmp_path), "crash")
    died = False
    inj = CrashInjector({point: hit})
    with inj:
        for i, delta in enumerate(deltas):
            try:
                srv.append(delta)
            except SimulatedCrash:
                died = True
                break
            if not _wait_quiescent(srv, sqs, i + 1, inj=inj):
                died = True
                break
    assert died or inj.fired, f"{point}[{hit}] never fired"
    srv.close(abandon=True)

    srv = SelectionServer.restore(
        root, _oracle, base_shards=_base_shards(),
        engine_kw=dict(ENGINE_KW, workers=workers),
        quotas={"t": 1_000_000})
    try:
        sqs = srv._registry.standing
        assert len(sqs) == len(QUERIES)
        assert srv.recovered_queries == len(QUERIES)
        # Resume protocol: the epoch number is the idempotency key — the
        # client re-issues exactly the appends the restored corpus shows
        # missing.
        for i in range(srv.plane.epoch, len(deltas)):
            srv.append(deltas[i])
        _wait_quiescent(srv, sqs, len(deltas))
        taus, masks, charged = _collect(srv, sqs)
    finally:
        srv.close()
    assert taus == ref_taus
    for got, ref in zip(masks, ref_masks):
        for sh, (g, r) in enumerate(zip(got, ref)):
            assert np.array_equal(g, r), f"shard {sh} bits diverged"
    # Zero oracle budget double-spent: certification + probes were never
    # re-run, and re-emission walks label nothing.
    assert charged == ref_charged


@pytest.mark.parametrize("point", APPEND_PATH_POINTS)
def test_server_crashpoint_acceptance(tmp_path, point, uncrashed_reference):
    _crash_restore_resume(tmp_path, 1, point, 1 if "journal" in point
                          else 0, uncrashed_reference(1))


@pytest.mark.slow
@pytest.mark.parametrize("workers", [4, 8])
@pytest.mark.parametrize("point", APPEND_PATH_POINTS)
def test_server_crashpoint_matrix(tmp_path, point, workers,
                                  uncrashed_reference):
    _crash_restore_resume(tmp_path, workers, point, 1 if "journal" in point
                          else 0, uncrashed_reference(workers))


def test_snapshot_crash_keeps_previous_snapshot(tmp_path):
    root = str(tmp_path / "dur")
    srv, sqs = _make_server(root, 1, str(tmp_path), "snap")
    before = srv.durable.read_snapshot()
    srv.append(_deltas()[0])
    _wait_quiescent(srv, sqs, 1)
    with CrashInjector({"pre_snapshot_publish": 0}):
        with pytest.raises(SimulatedCrash):
            srv.snapshot()
    assert srv.durable.read_snapshot() == before
    srv.close(abandon=True)


def test_restore_spends_nothing_with_audited_watch(tmp_path):
    """Restore re-adopts an audited watch from its snapshot: the tenant
    ledger sits exactly at its snapshot balance (certification and the
    reference probe are NOT re-run), and post-restore epochs are audited
    with the same per-epoch keys the uncrashed scheduler would use."""
    root = str(tmp_path / "dur")
    eng = SelectionEngine(_base_shards(), **ENGINE_KW)
    srv = SelectionServer(eng, _oracle, durable=root,
                          quotas={"t": 1_000_000}, sentinel_probe_budget=64)
    sq = srv.subscribe(QUERIES[0], tenant="t", key=jax.random.PRNGKey(9),
                       sink=BitmaskStore(str(tmp_path / "a.bits")),
                       audit=True)
    sq.wait_certified(timeout=120)
    deadline = time.monotonic() + 120
    while not srv._watches:        # the scheduler attaches the watch
        assert time.monotonic() < deadline
        time.sleep(0.01)
    srv.append(_deltas()[0])
    while srv._watches[0][3] < 1 or srv._registry.has_pending():
        assert time.monotonic() < deadline
        time.sleep(0.01)
    snap = srv.snapshot()
    snap_charged = snap["tenants"]["t"]["charged"]
    assert snap_charged > 0
    assert snap["watches"] and snap["watches"][0]["last_audited"] == 1
    srv.close(abandon=True)

    srv = SelectionServer.restore(
        root, _oracle, base_shards=_base_shards(), engine_kw=ENGINE_KW,
        quotas={"t": 1_000_000}, sentinel_probe_budget=64)
    try:
        assert srv.stats().tenants["t"].oracle_charged == snap_charged
        assert srv._watches and srv._watches[0][3] == 1
        [sq2] = srv._registry.standing
        assert sq2.tau == sq.tau and sq2.certified
        srv.append(_deltas()[1])
        deadline = time.monotonic() + 120
        while srv._watches[0][3] < 2 or srv._registry.has_pending():
            assert time.monotonic() < deadline
            time.sleep(0.01)
        st = srv.stats()
        assert st.sentinel_checks == 1          # epoch 2 only: 1 was done
        # The probe hit the oracle. Its 64 draws are with replacement,
        # so a repeated record is answered by the cache, not relabeled.
        assert st.records_labeled > 0
        # Tenant balance still equals the snapshot's: probes ride their
        # own throwaway ledger, and nothing certified was re-charged.
        assert st.tenants["t"].oracle_charged == snap_charged
    finally:
        srv.close()


def test_fresh_server_refuses_crashed_journal(tmp_path):
    root = str(tmp_path / "dur")
    dur = DurabilityPlane(root)
    dur.record_append(_deltas()[0], epoch=1)
    dur.close()
    with SelectionEngine(_base_shards(), **ENGINE_KW) as eng:
        with pytest.raises(ValueError, match="restore"):
            SelectionServer(eng, _oracle, durable=root, own_engine=False)


def test_server_stats_report_durability(tmp_path):
    root = str(tmp_path / "dur")
    srv, sqs = _make_server(root, 1, str(tmp_path), "stats")
    srv.append(_deltas()[0])
    _wait_quiescent(srv, sqs, 1)
    srv.snapshot()
    st = srv.stats()
    assert st.durable and st.journal_records == 1 and st.journal_bytes > 0
    assert st.snapshots == 2
    assert st.epochs_freed >= 1 and st.epochs_live >= 1
    assert "durable: on" in st.format()
    srv.close()
