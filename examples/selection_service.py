"""End-to-end selection service — the paper's full pipeline, served.

    PYTHONPATH=src python examples/selection_service.py

1. TRAIN a small proxy LM to detect a planted marker n-gram (the filter
   predicate) from batched token streams.
2. SERVE: run batched prefill scoring over the whole corpus with the
   pjit-able serve_prefill step, writing A(x) into a memory-mapped
   ScoreStore (the production scoring plane in miniature).
3. SELECT: build a SelectionEngine directly on the memory-mapped
   ScoreStore shard and serve a *batch* of RT / PT / JT SUPG queries
   through a `SelectionServer` daemon — one cached sketch + sampling
   state AND one shared, batched labeling channel amortized across every
   client (concurrent query plans coalesce their oracle requests into
   micro-batches; records labeled for one tenant's query answer the
   others from the cache for free), with admission control, per-tenant
   oracle quotas, and a token bucket pacing the labeling channel —
   verifying the statistical guarantees and comparing against the U-NoCI
   baseline used by prior systems, then printing the server's
   observability snapshot (`ServerStats`).
   The first query is served *streamed*: results reach the client
   incrementally through a SelectionStream (chunked shard-parallel
   emission; no full-corpus mask is ever materialized), which is how a
   service would page results out of a billion-record store.
"""
import tempfile

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core import (SUPGQuery, array_oracle, precision_of, recall_of)
from repro.core.engine import SelectionEngine
from repro.core.queries import JointSUPGQuery
from repro.data import synthetic
from repro.data.pipeline import ScoreStore, SelectionStream
from repro.serve import SelectionServer
from repro.launch import serve as servelib
from repro.launch import train as trainlib
from repro.launch.compile_cache import enable_compile_cache
from repro.models import model
from repro.optim import adamw

CFG = ModelConfig(name="selector-proxy", family="dense", num_layers=2,
                  d_model=96, num_heads=4, num_kv_heads=2, d_ff=256,
                  vocab_size=128, dtype="float32")
CORPUS, SEQ = 20_000, 48


def train_proxy(tokens, labels, steps=120):
    params = model.init(jax.random.PRNGKey(0), CFG)
    opts = trainlib.TrainOptions(adamw=adamw.AdamWConfig(
        lr=3e-3, warmup_steps=10, total_steps=steps, weight_decay=0.0))
    step_fn = jax.jit(trainlib.make_train_step(CFG, opts))
    opt = adamw.init(params)
    rng = np.random.default_rng(0)
    pos_pool = np.nonzero(labels > 0.5)[0]
    neg_pool = np.nonzero(labels <= 0.5)[0]
    for i in range(steps):
        # class-balanced batches: at 2% TPR an unbalanced stream collapses
        # the proxy to the majority class (the standard practitioner fix)
        idx = np.concatenate([rng.choice(pos_pool, 32),
                              rng.choice(neg_pool, 32)])
        bt = tokens[idx]
        y = labels[idx].astype(np.int32)
        # class label at every position: post-marker positions carry signal
        lab = np.broadcast_to(y[:, None], bt.shape).astype(np.int32)
        params, opt, m = step_fn(params, opt,
                                 {"tokens": jnp.asarray(bt),
                                  "labels": jnp.asarray(lab)})
        if (i + 1) % 40 == 0:
            print(f"  train step {i+1}: loss {float(m['loss']):.4f}")
    return params


def main():
    enable_compile_cache()
    print("[1/3] building corpus + training proxy")
    tokens, labels = synthetic.make_token_corpus(CORPUS, SEQ, CFG.vocab_size,
                                                 positive_rate=0.02, seed=1)
    params = train_proxy(tokens, labels)

    print("[2/3] batched scoring service over the corpus")
    serve_fn = jax.jit(servelib.make_serve_prefill(CFG, target_token=1))
    store = ScoreStore(tempfile.mktemp(suffix=".scores"), CORPUS,
                       create=True)
    bs = 512
    for off in range(0, CORPUS, bs):
        scores = serve_fn(params, {"tokens": jnp.asarray(
            tokens[off:off + bs])})
        store.write(off, np.asarray(scores))
    scores = store.read()
    truth = labels > 0.5
    print(f"  scored {store.num_scored} records; "
          f"mean A(x) pos={scores[truth].mean():.3f} "
          f"neg={scores[~truth].mean():.3f}")

    print("[3/3] SUPG queries via the SelectionServer daemon "
          "(budget=1500, delta=5%)")
    # The engine consumes the memory-mapped store directly (zero-copy) and
    # builds its sketch + chunk-level sampling state exactly once for the
    # whole service lifetime; workers=2 drives the chunked sketch/emission
    # walks through the thread pool (results are identical at any worker
    # count). The context managers guarantee the worker pool, session
    # pool, and drain thread are released even if a query blows up —
    # the original version leaked the engine on the error path.
    oracle = array_oracle(labels)
    with SelectionEngine([store], num_bins=4096, workers=2) as engine:
        # Streamed serving: the client consumes selection chunks as the
        # engine emits them, long before the query finishes — at
        # production scale this is the only shape that works (no
        # full-corpus mask exists to return).
        stream_q = SUPGQuery(target="recall", gamma=0.9, delta=0.05,
                             budget=1500, method="is")
        stream = SelectionStream(
            lambda sink: engine.run(jax.random.PRNGKey(3), oracle,
                                    stream_q, sink=sink,
                                    chunk_records=4096))
        streamed = 0
        for i, (shard_id, gids, folded) in enumerate(stream):
            streamed += gids.size
            kind = "folded-positives" if folded else "chunk"
            print(f"  stream[{i}] shard={shard_id} {kind:16s} "
                  f"+{gids.size:5d} (total {streamed})")
        print(f"  streamed selection done: {streamed} records, "
              f"tau={stream.result.tau:.4f} (counts held by the sink; "
              f"no mask materialized)")

        # Serve the batch through the daemon: concurrent clients submit
        # on behalf of tenants, admission control bounds in-flight plans,
        # per-tenant BudgetLedger quotas meter the oracle, and a token
        # bucket paces the shared labeling channel (the paper's §4.1
        # rate-limited-oracle model, made literal). All plans' oracle
        # requests funnel into one BatchingOracle, so a record labeled
        # for one tenant answers the others from the cache for free.
        batch = [SUPGQuery(target=target, gamma=gamma, delta=0.05,
                           budget=1500, method=method)
                 for target, gamma in (("recall", 0.9),
                                       ("precision", 0.75))
                 for method in ("is", "noci")]
        batch.append(JointSUPGQuery(gamma_recall=0.9, stage_budget=1500))
        keys = jax.random.split(jax.random.PRNGKey(3), len(batch))
        tenants = ["supg", "baseline", "supg", "baseline", "joint"]
        with SelectionServer(engine, oracle, own_engine=False,
                             max_inflight=4, max_batch=4096,
                             rate=500_000, burst=50_000,
                             quotas={"supg": 10_000, "baseline": 10_000,
                                     "joint": 40_000}) as server:
            handles = [server.submit(q, tenant=t, key=k)
                       for q, t, k in zip(batch, tenants, keys)]
            results = [h.result(timeout=600) for h in handles]
            stats = server.stats()
    print("  --- ServerStats ---")
    for line in stats.format().splitlines():
        print(f"  {line}")
    for q, sel in zip(batch, results):
        mask = np.concatenate(sel.masks)
        selected = np.nonzero(mask)[0]
        p = precision_of(selected, truth)
        r = recall_of(selected, truth)
        if isinstance(q, JointSUPGQuery):
            ok = ("MET " if r >= q.gamma_recall
                  and p >= q.gamma_precision else "MISS")
            print(f"  joint r>={q.gamma_recall:.0%} p>="
                  f"{q.gamma_precision:.0%} [JT    ] {ok} "
                  f"precision={p:.3f} recall={r:.3f} "
                  f"|R|={len(selected)} calls={sel.oracle_calls}")
            continue
        a = r if q.target == "recall" else p
        tag = "SUPG" if q.method == "is" else "U-NoCI"
        ok = "MET " if a >= q.gamma else "MISS"
        print(f"  {q.target:9s}>= {q.gamma:.0%} [{tag:6s}] {ok} "
              f"precision={p:.3f} recall={r:.3f} "
              f"|R|={len(selected)} calls={sel.oracle_calls}")


if __name__ == "__main__":
    main()
