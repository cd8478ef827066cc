"""The program's profiler spans (`jax.profiler.TraceAnnotation`, TraceMe
events named `supg.*`): one RT query through a `SelectionServer` and one
append, traced and read back from the `.xplane.pb`. Every span is there,
on the thread that does the work, under its parent, with the request id
and the counts the engine's own counters give; and tracing changes no
answer."""
import glob
import os

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.core.engine import SelectionEngine
from repro.core.queries import SUPGQuery
from repro.kernels.threshold_select import ops as select_ops
from repro.serve import SelectionServer

RECORDS, SHARDS, CHUNK, BUDGET = 60_000, 3, 4096, 600
QUERY = SUPGQuery(target="recall", gamma=0.9, delta=0.05, budget=BUDGET,
                  method="is")
KEY = jax.random.PRNGKey(14)

SPANS = ("supg.server.turn", "supg.admit", "supg.round", "supg.drain_wait",
         "supg.sample", "supg.sample.rng", "supg.sample.chunk", "supg.bound",
         "supg.emit", "supg.emit.chunk", "supg.emit.stitch",
         "supg.oracle.drain", "supg.oracle.call", "supg.append",
         "supg.append.sketch")


def corpus():
    rng = np.random.default_rng(14)
    scores = rng.beta(0.05, 1.0, RECORDS).astype(np.float32)
    labels = (rng.random(RECORDS) < scores).astype(np.float32)
    return scores, labels


def serve(scores, labels):
    engine = SelectionEngine(np.array_split(scores, SHARDS), num_bins=64,
                             chunk_records=CHUNK, workers=4,
                             clamp_workers=False, use_kernel=False)
    return SelectionServer(engine, lambda idx: labels[np.asarray(idx)],
                           max_inflight=2)


def read_spans(log_dir):
    """Every `supg.*` host event as (name, start_s, end_s, args, thread),
    the thread being the event's line in the host plane."""
    path, = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line_no, line in enumerate(plane.lines):
            for ev in line.events:
                if ev.name.startswith("supg."):
                    s = ev.start_ns * 1e-9
                    out.append((ev.name, s, s + ev.duration_ns * 1e-9,
                                dict(ev.stats), line_no))
    return out


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """One query and one append under a profiler session, beside the
    same query on a twin server that no profiler watches."""
    scores, labels = corpus()
    extra = np.random.default_rng(15).beta(0.05, 1.0, 5000).astype(
        np.float32)
    server = serve(scores, labels)
    log_dir = str(tmp_path_factory.mktemp("trace"))
    with jax.profiler.trace(log_dir):
        sel = server.submit(QUERY, key=KEY).result(timeout=120)
        server.append(extra)
    stats = server._sessions[0].stats
    channel = server.channel
    counts = dict(plan_steps=stats.plan_steps, fused_walks=stats.fused_walks,
                  labeled=channel.records_labeled, hits=channel.cache_hits,
                  fn_calls=channel.fn_calls)
    answer = (sel.tau, [sel.indices(sh) for sh in range(SHARDS)],
              sel.sampled_positive_global)
    server.close()
    twin = serve(scores, labels)
    plain = twin.submit(QUERY, key=KEY).result(timeout=120)
    twin_answer = (plain.tau, [plain.indices(sh) for sh in range(SHARDS)],
                   plain.sampled_positive_global)
    twin.close()
    return dict(spans=read_spans(log_dir), counts=counts, answer=answer,
                twin=twin_answer, scores=scores, extra=extra)


def named(spans, name):
    return [sp for sp in spans if sp[0] == name]


def inside(child, parents):
    return any(p[1] <= child[1] and child[2] <= p[2] for p in parents)


def test_every_span_is_recorded(traced):
    names = {sp[0] for sp in traced["spans"]}
    assert set(SPANS) <= names


def test_spans_run_on_the_threads_that_do_the_work(traced):
    spans = traced["spans"]
    sched = {sp[4] for sp in named(spans, "supg.round")}
    assert len(sched) == 1            # the server's scheduler thread
    for name in ("supg.server.turn", "supg.admit", "supg.sample",
                 "supg.bound", "supg.emit", "supg.drain_wait"):
        assert {sp[4] for sp in named(spans, name)} == sched, name
    pool = ({sp[4] for sp in named(spans, "supg.sample.chunk")}
            | {sp[4] for sp in named(spans, "supg.emit.chunk")})
    assert pool and not pool & sched  # the engine's worker threads
    drain = {sp[4] for sp in named(spans, "supg.oracle.drain")}
    assert len(drain) == 1 and not drain & (sched | pool)
    assert {sp[4] for sp in named(spans, "supg.oracle.call")} == drain
    caller = {sp[4] for sp in named(spans, "supg.append")}
    assert len(caller) == 1 and not caller & sched
    assert {sp[4] for sp in named(spans, "supg.append.sketch")} == caller


def test_children_lie_inside_their_parents(traced):
    spans = traced["spans"]
    for child, parent in (("supg.admit", "supg.server.turn"),
                          ("supg.round", "supg.server.turn"),
                          ("supg.drain_wait", "supg.round"),
                          ("supg.sample", "supg.round"),
                          ("supg.sample.rng", "supg.sample"),
                          ("supg.sample.chunk", "supg.sample"),
                          ("supg.bound", "supg.round"),
                          ("supg.emit", "supg.round"),
                          ("supg.emit.chunk", "supg.emit"),
                          ("supg.emit.stitch", "supg.emit.chunk"),
                          ("supg.oracle.call", "supg.oracle.drain"),
                          ("supg.append.sketch", "supg.append")):
        parents = named(spans, parent)
        for sp in named(spans, child):
            assert inside(sp, parents), (child, parent)


def test_one_request_id_follows_the_query(traced):
    spans = traced["spans"]
    qs = {name: {sp[3]["q"] for sp in named(spans, name)}
          for name in ("supg.admit", "supg.sample", "supg.sample.rng",
                       "supg.sample.chunk", "supg.bound", "supg.emit")}
    q, = qs["supg.admit"]
    assert q > 0
    assert all(v == {q} for v in qs.values()), qs


def test_span_arguments_are_the_engine_counts(traced):
    spans, counts = traced["spans"], traced["counts"]
    scores = traced["scores"]
    turns = named(spans, "supg.server.turn")
    assert sum(sp[3]["admitted"] for sp in turns) == 1
    assert sum(sp[3]["finished"] for sp in turns) == 1
    admit, = named(spans, "supg.admit")
    assert admit[3]["queued_us"] >= 0
    rounds = named(spans, "supg.round")
    assert sum(sp[3]["plans"] for sp in rounds) == counts["plan_steps"]
    assert sum(sp[3]["walks"] for sp in rounds) == counts["fused_walks"]

    # Sampling: the chunks the draws fell in, drawn again with no trace.
    with SelectionEngine(np.array_split(scores, SHARDS), num_bins=64,
                         chunk_records=CHUNK, use_kernel=False) as eng:
        idx, _ = eng.draw_sample(KEY, BUDGET, "sqrt")
        shard = np.searchsorted(eng.offsets, idx, side="right") - 1
        chunk = (idx - eng.offsets[shard]) // CHUNK
        fell_in = {(int(a), int(b)) for a, b in zip(shard, chunk)}
        total_chunks = eng.plan.total_chunks
        # the distinct 1,024-record blocks hit, and the records they hold
        block = (idx - eng.offsets[shard] - chunk * CHUNK) // 1024
        hit = {(int(a), int(b), int(c))
               for a, b, c in zip(shard, chunk, block)}
        hit_records = sum(min(1024, eng.plan.shard_spans(a)[b].size - c * 1024)
                          for a, b, c in hit)
    sample, = named(spans, "supg.sample")
    assert sample[3]["draws"] == BUDGET
    assert sample[3]["chunks"] == len(fell_in)
    resolved = named(spans, "supg.sample.chunk")
    assert {(sp[3]["shard"], sp[3]["chunk"]) for sp in resolved} == fell_in
    assert sum(sp[3]["draws"] for sp in resolved) == BUDGET
    assert sum(sp[3]["blocks"] for sp in resolved) == len(hit)
    assert sum(sp[3]["records"] for sp in resolved) == hit_records
    assert hit_records <= min(BUDGET * 1024, RECORDS)
    assert sample[3]["records"] == hit_records

    # Labels: the channel's own counters.
    wait, = named(spans, "supg.drain_wait")
    assert wait[3]["records"] == BUDGET
    drain, = named(spans, "supg.oracle.drain")
    assert drain[3]["records"] == BUDGET
    assert drain[3]["new"] == counts["labeled"]
    assert drain[3]["cache_hits"] == counts["hits"]
    calls = named(spans, "supg.oracle.call")
    assert len(calls) == counts["fn_calls"]
    assert sum(sp[3]["records"] for sp in calls) == counts["labeled"]

    # Emission: one walk over every chunk of the corpus.
    emit, = named(spans, "supg.emit")
    assert emit[3]["walks"] == 1 and emit[3]["spans"] == total_chunks
    chunks = named(spans, "supg.emit.chunk")
    assert len(chunks) == total_chunks
    tau = traced["answer"][0]
    assert sum(sp[3]["selected"] for sp in chunks) == int(
        np.count_nonzero(scores >= tau))

    append, = named(spans, "supg.append")
    assert append[3] == {"shards": 1, "records": traced["extra"].size}
    sketch, = named(spans, "supg.append.sketch")
    assert sketch[3]["records"] == traced["extra"].size


def test_tracing_changes_no_answer(traced):
    (tau, idx, pos), (tau2, idx2, pos2) = traced["answer"], traced["twin"]
    assert tau == tau2
    assert all(np.array_equal(a, b) for a, b in zip(idx, idx2))
    assert np.array_equal(pos, pos2)


def test_kernel_path_stitch_span(tmp_path):
    scores = np.linspace(0.0, 1.0, 2048, dtype=np.float32)
    with jax.profiler.trace(str(tmp_path)):
        got = select_ops.threshold_select(scores, 0.5, backend="interpret")
    assert np.array_equal(got, np.flatnonzero(scores >= 0.5))
    assert len(named(read_spans(str(tmp_path)), "supg.emit.stitch")) == 1


def test_a_direct_precision_query_carries_one_request_id(tmp_path):
    """`engine.run` numbers its plan too; a two-stage PT query opens one
    `supg.sample` for its importance draw and one for its region draw."""
    scores, labels = corpus()
    query = SUPGQuery(target="precision", gamma=0.9, delta=0.05,
                      budget=BUDGET, method="is")
    with SelectionEngine(np.array_split(scores, SHARDS), num_bins=64,
                         chunk_records=CHUNK, workers=4,
                         clamp_workers=False, use_kernel=False) as eng:
        with jax.profiler.trace(str(tmp_path)):
            sel = eng.run(KEY, lambda idx: labels[np.asarray(idx)], query)
        plain = eng.run(KEY, lambda idx: labels[np.asarray(idx)], query)
    assert sel.tau == plain.tau
    spans = read_spans(str(tmp_path))
    samples = named(spans, "supg.sample")
    assert [sp[3]["draws"] for sp in samples] == [BUDGET // 2,
                                                  BUDGET - BUDGET // 2]
    qs = {sp[3]["q"] for sp in samples + named(spans, "supg.bound")
          + named(spans, "supg.emit") + named(spans, "supg.sample.chunk")}
    assert len(qs) == 1 and qs.pop() > 0
    region = samples[1]
    resolved = [sp for sp in named(spans, "supg.sample.chunk")
                if inside(sp, [region])]
    assert len(resolved) == region[3]["chunks"] > 0
