"""One pass of the SUPG main path on one TPU chip, with every result checked.

    python3 chip_smoke.py                            # needs one TPU chip
    JAX_PLATFORMS=cpu python3 chip_smoke.py --tiny   # CPU rehearsal

Phase 1, scoring: smollm-360m at its published widths, with random bf16
weights from --seed, scores 4,096 synthetic token records of 512 tokens
through ``jax.jit(make_serve_prefill(cfg))`` in batches of 64 into a
`ScoreStore`. A few records are checked against the same forward pass in
float32 jax.numpy.

Phase 2, selection: 1e8 Beta(0.01, 1) proxy scores (SUPG §7's synthetic
setting, about 1% positive) in four shards. A `SelectionEngine` at 4,096
bins under a `SelectionServer` serves one RT, one PT and one JT query
(gamma 0.9, delta 0.05, budget 3,000). The scored shard from phase 1 is
then appended with its marker labels, and a subscribed standing query
catches up over it.

Each check prints one line. A failed check raises, so the script exits
non-zero and prints no result. The last line of standard output is one
JSON object naming the device. Without a TPU the default run exits 2
before any work; ``--tiny`` shrinks every size so the same code runs on
the CPU with the Pallas kernels in interpret mode.
"""
from __future__ import annotations

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import argparse  # noqa: E402
import contextlib  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import tempfile  # noqa: E402
import threading  # noqa: E402
import time  # noqa: E402
from typing import Optional  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import smollm_360m  # noqa: E402
from repro.core import array_oracle, binned, queries  # noqa: E402
from repro.core.engine import SelectionEngine  # noqa: E402
from repro.core.queries import JointSUPGQuery, SUPGQuery  # noqa: E402
from repro.data import synthetic  # noqa: E402
from repro.data.pipeline import IndexSink, ScoreStore  # noqa: E402
from repro.kernels.threshold_select.threshold_select import (  # noqa: E402
    threshold_select_rows)
from repro.launch import serve as servelib  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.models import model  # noqa: E402
from repro.serve import SelectionServer  # noqa: E402

NUM_BINS = 4096
GAMMA, DELTA, BUDGET = 0.9, 0.05, 3000
# bf16 keeps 8 significant bits (relative rounding 2^-9 per operation);
# through 32 residual layers the last-position logits drift by about 2%
# (relative L2) from the float32 forward. A 5% bound leaves room for that
# and still fails a lower precision such as fp8 (2^-4 per operation).
LOGIT_RTOL = 5e-2
# Per-chunk sums accumulate in float32 over at most 32,768 row partials
# per bin (random-walk error near 1e-5). A bfloat16-rounded operand errs
# by up to 2e-3 in a bin of few records, so 1e-4 catches a lost HIGHEST.
SUM_RTOL = 1e-4
RESULT_TIMEOUT_S = 900.0


@dataclasses.dataclass(frozen=True)
class Sizes:
    records: int                  # token records scored in phase 1
    seq_len: int                  # tokens per record
    batch: int                    # records per prefill call
    ref_records: int              # records checked against float32
    corpus: int                   # Beta records in phase 2
    shards: int
    chunk_records: Optional[int]  # None: the engine's default chunk


FULL = Sizes(records=4096, seq_len=512, batch=64, ref_records=8,
             corpus=100_000_000, shards=4, chunk_records=None)
TINY = Sizes(records=128, seq_len=64, batch=64, ref_records=4,
             corpus=120_000, shards=4, chunk_records=1 << 14)


class SmokeFailure(RuntimeError):
    """A check of the smoke run failed."""


def check(ok, name: str, detail: str) -> None:
    if not ok:
        raise SmokeFailure(f"check {name} FAILED: {detail}")
    print(f"check {name}: ok ({detail})", flush=True)


class CompileClock:
    """Sums XLA compile durations (any thread) and counts compilations,
    through `jax.monitoring`. Tracing and lowering stay in the run time:
    nested jits report them more than once."""

    COMPILE = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self._lock = threading.Lock()
        self.seconds = 0.0
        self.compilations = 0

    def __call__(self, event: str, duration_secs: float, **_kw) -> None:
        if event != self.COMPILE:
            return
        with self._lock:
            self.seconds += duration_secs
            self.compilations += 1

    def __enter__(self) -> "CompileClock":
        jax.monitoring.register_event_duration_secs_listener(self)
        return self

    def __exit__(self, *exc) -> None:
        jax.monitoring.unregister_event_duration_listener(self)

    @contextlib.contextmanager
    def phase(self, name: str):
        t0 = time.perf_counter()
        c0, n0 = self.seconds, self.compilations
        yield
        wall = time.perf_counter() - t0
        comp = self.seconds - c0
        print(f"phase {name}: wall {wall:.3f} s = compile {comp:.3f} s "
              f"+ run {wall - comp:.3f} s; "
              f"{self.compilations - n0} compilations", flush=True)


# -- phase 1: proxy scoring ------------------------------------------------

def score_records(cfg, sz: Sizes, seed: int, store_path: str):
    """Score the token corpus through the serving prefill into a store."""
    tokens, labels = synthetic.make_token_corpus(
        sz.records, sz.seq_len, vocab=cfg.vocab_size, seed=seed)
    params = jax.jit(model.init, static_argnums=1)(
        jax.random.PRNGKey(seed), cfg)
    serve = jax.jit(servelib.make_serve_prefill(cfg))
    store = ScoreStore(store_path, sz.records, create=True)
    for off in range(0, sz.records, sz.batch):
        batch = {"tokens": jnp.asarray(tokens[off:off + sz.batch])}
        store.write(off, np.asarray(serve(params, batch)))
    return params, tokens, labels, store


def _last_logits(cfg):
    return jax.jit(lambda p, t: model.apply_train(p, cfg, t)[0][:, -1]
                   .astype(jnp.float32))


def check_scores(cfg, params, tokens, store, n: int) -> None:
    """The scorer's output against a float32 jax.numpy forward pass."""
    got = store.read()
    check(np.isfinite(got).all() and (got >= 0).all() and (got <= 1).all(),
          "scores-range", f"{got.size} scores finite in [0, 1]")
    toks = jnp.asarray(tokens[:n])
    logits = np.asarray(_last_logits(cfg)(params, toks), np.float64)
    cfg32 = dataclasses.replace(cfg, dtype="float32")
    p32 = jax.tree.map(lambda x: x.astype(jnp.float32), params)
    with jax.default_matmul_precision("highest"):
        ref_logits = np.asarray(_last_logits(cfg32)(p32, toks), np.float64)
        ref = np.asarray(jax.jit(servelib.make_serve_prefill(cfg32))(
            p32, {"tokens": toks}), np.float64)
    rel = (np.linalg.norm(logits - ref_logits, axis=1)
           / np.linalg.norm(ref_logits, axis=1))
    check(rel.max() <= LOGIT_RTOL, "scorer-logits-vs-float32",
          f"max relative L2 error {rel.max():.4e} over {n} records, "
          f"tolerance {LOGIT_RTOL}")
    # log p = logit_t - logsumexp(logits) moves by at most twice the
    # largest logit error; 1e-3 covers the float32 softmax itself.
    # Scores below 1e-30 (float32 underflow) compare absolutely.
    bound = 2.0 * np.abs(logits - ref_logits).max(axis=1) + 1e-3
    sys_s = got[:n].astype(np.float64)
    ok = np.abs(sys_s - ref) <= np.expm1(bound) * ref + 1e-30
    check(ok.all(), "scorer-scores-vs-float32",
          f"{n} records within exp(2 max|dlogit|) of the float32 score; "
          f"{int((ref >= 1e-30).sum())} of them above 1e-30")


# -- phase 2: selection ----------------------------------------------------

def check_kernel_paths(engine: SelectionEngine, on_tpu: bool) -> None:
    """Prove the main path runs the compiled kernels, not a reference."""
    if not on_tpu:
        print(f"kernels: interpret mode off TPU (select backend "
              f"{engine.select_backend!r})", flush=True)
        return
    x = jax.ShapeDtypeStruct((engine.chunk_records,), jnp.float32)
    sketch_hlo = jax.jit(binned.build_sketch, static_argnums=1).lower(
        x, NUM_BINS).as_text()
    select_hlo = threshold_select_rows.lower(x, 0.5).as_text()
    check("tpu_custom_call" in sketch_hlo and "tpu_custom_call" in select_hlo
          and engine.select_backend == "pallas", "compiled-kernels",
          "build_sketch and threshold_select lower to tpu_custom_call; "
          "engine select backend 'pallas'")


def check_sketch(engine: SelectionEngine, shards) -> None:
    """Every chunk's kernel sketch against numpy; merged totals too."""
    total = np.zeros(NUM_BINS, np.int64)
    worst = 0.0
    mismatched = []
    for sp in engine.plan:
        chunk = shards[sp.shard_id][sp.start:sp.stop]
        sk = binned.chunk_sketch_stats(chunk, NUM_BINS)[0]
        ids = np.minimum((np.clip(chunk, 0.0, 1.0) * np.float32(NUM_BINS))
                         .astype(np.int32), NUM_BINS - 1)
        counts = np.bincount(ids, minlength=NUM_BINS)
        if not np.array_equal(np.asarray(sk.counts).astype(np.int64),
                              counts):
            mismatched.append((sp.shard_id, sp.chunk_id))
        c64 = chunk.astype(np.float64)
        for got, want in ((sk.sum_w, np.bincount(ids, np.sqrt(c64),
                                                 NUM_BINS)),
                          (sk.sum_a, np.bincount(ids, c64, NUM_BINS))):
            err = np.abs(np.asarray(got, np.float64) - want)
            worst = max(worst, float((err / np.maximum(want, 1e-300))
                                     .max()))
        total += counts
    n_chunks = engine.plan.total_chunks
    check(not mismatched, "sketch-chunk-counts",
          f"{n_chunks} chunks against a numpy int64 histogram, exact; "
          f"(shard, chunk) differing: {mismatched}")
    check(worst <= SUM_RTOL, "sketch-chunk-sums",
          f"max relative error {worst:.3e} vs float64, tolerance {SUM_RTOL}")
    # Each float32 add of the merge rounds by at most 2^-24 relative, and
    # bins above 2^24 records are no longer exact.
    rtol = (n_chunks + len(shards)) * 2.0 ** -24
    merged = np.asarray(engine.sketch.counts, np.float64)
    err = float((np.abs(merged - total) / np.maximum(total, 1)).max())
    check(err <= rtol and int(total.sum()) == engine.n_total,
          "sketch-merged-counts",
          f"max relative error {err:.3e}, tolerance {rtol:.3e}; largest "
          f"bin {int(total.max())} records")


def _global_indices(sel) -> np.ndarray:
    offsets = np.concatenate([[0], np.cumsum(sel.shard_sizes)])
    return np.concatenate([sel.indices(sh) + offsets[sh]
                           for sh in range(sel.num_shards)])


def check_query(name, sel, ref_sel, scores, truth) -> None:
    """Emitted set, target and agreement with the plain reference."""
    emitted = _global_indices(sel)
    want = np.union1d(np.flatnonzero(scores >= sel.tau),
                      sel.sampled_positive_global)
    if name == "JT":
        want = want[truth[want]]
    check(np.array_equal(emitted, want), f"{name}-emitted-set",
          f"{emitted.size} records = {{A >= tau}} + folded positives"
          f"{' - verified negatives' if name == 'JT' else ''}, "
          f"tau {sel.tau:.6g}")
    rec = queries.recall_of(emitted, truth)
    prec = queries.precision_of(emitted, truth)
    ref_rec = queries.recall_of(ref_sel, truth)
    ref_prec = queries.precision_of(ref_sel, truth)
    if name == "RT":
        met, ref_met = rec >= GAMMA, ref_rec >= GAMMA
    elif name == "PT":
        met, ref_met = prec >= GAMMA, ref_prec >= GAMMA
    else:
        met = rec >= GAMMA and prec == 1.0
        ref_met = ref_rec >= GAMMA and ref_prec == 1.0
    check(met, f"{name}-target", f"recall {rec:.4f} precision {prec:.4f}, "
          f"gamma {GAMMA}")
    ratio = max(emitted.size, 1) / max(ref_sel.size, 1)
    check(ref_met and 0.2 < ratio < 5.0, f"{name}-vs-reference",
          f"reference recall {ref_rec:.4f} precision {ref_prec:.4f}; "
          f"|R| {emitted.size} vs reference {ref_sel.size}")


def run_reference(name, query, key, scores, oracle) -> np.ndarray:
    if name == "JT":
        return queries.run_joint_query(
            key, scores, oracle, query.gamma_recall, query.gamma_precision,
            delta=query.delta, stage_budget=query.stage_budget).selected
    return queries.run_query(key, scores, oracle, query).selected


def wait_for(pred, what: str) -> None:
    deadline = time.monotonic() + RESULT_TIMEOUT_S
    while not pred():
        if time.monotonic() > deadline:
            raise SmokeFailure(f"{what} did not happen within "
                               f"{RESULT_TIMEOUT_S} s")
        time.sleep(0.05)


# -- driver ----------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--tiny", action="store_true",
                    help="shrink every size; runs on the CPU")
    args = ap.parse_args(argv)

    dev = jax.devices()[0]
    on_tpu = dev.platform == "tpu"
    if not on_tpu and not args.tiny:
        print(f"chip_smoke: no TPU (JAX found {dev.platform!r}); the full "
              f"run needs one chip, --tiny rehearses on the CPU",
              file=sys.stderr)
        return 2
    sz = TINY if args.tiny else FULL
    cfg = (dataclasses.replace(smollm_360m.smoke(), dtype="bfloat16")
           if args.tiny else smollm_360m.CONFIG)
    print(f"device: {dev.platform} {dev.device_kind} x{len(jax.devices())}; "
          f"model {cfg.name} ({cfg.num_layers}L d={cfg.d_model} "
          f"{cfg.dtype}); corpus {sz.corpus} records", flush=True)

    with CompileClock() as clock, tempfile.TemporaryDirectory() as tmp:
        with clock.phase("scoring"):
            params, tokens, tok_labels, store = score_records(
                cfg, sz, args.seed, os.path.join(tmp, "scored.f32"))
        with clock.phase("scoring-check"):
            check_scores(cfg, params, tokens, store, sz.ref_records)
        del params

        with clock.phase("corpus"):
            ds = synthetic.make_beta(sz.corpus, 0.01, 1.0, seed=args.seed)
            scores = ds.scores
            shards = np.array_split(scores, sz.shards)
            labels = np.concatenate([ds.labels, tok_labels])
            truth = ds.labels > 0.5
            oracle = array_oracle(labels)
        with clock.phase("engine-build"):
            engine = SelectionEngine(
                shards, num_bins=NUM_BINS, chunk_records=sz.chunk_records,
                select_backend=None if on_tpu else "interpret")
        with clock.phase("sketch-check"):
            check_kernel_paths(engine, on_tpu)
            check_sketch(engine, shards)

        names = ("RT", "PT", "JT")
        batch = (SUPGQuery(target="recall", gamma=GAMMA, delta=DELTA,
                           budget=BUDGET),
                 SUPGQuery(target="precision", gamma=GAMMA, delta=DELTA,
                           budget=BUDGET),
                 JointSUPGQuery(gamma_recall=GAMMA, delta=DELTA,
                                stage_budget=BUDGET))
        keys = jax.random.split(jax.random.PRNGKey(args.seed + 1), 4)
        standing_sink = IndexSink()
        with SelectionServer(engine, oracle) as server:
            with clock.phase("serve-queries"):
                sq = server.subscribe(batch[0], key=keys[3],
                                      sink=standing_sink)
                handles = [server.submit(q, key=k)
                           for q, k in zip(batch, keys)]
                results = [h.result(timeout=RESULT_TIMEOUT_S)
                           for h in handles]
                tau_sq = sq.wait_certified(timeout=RESULT_TIMEOUT_S)
            with clock.phase("append-catch-up"):
                epoch = server.append(store)
                wait_for(lambda: sq.emissions >= 1 or sq.reemit_failures,
                         "the standing query's catch-up")
            stats = server.stats()
        check(sq.reemit_failures == 0 and sq.epoch == epoch,
              "standing-catch-up", f"epoch {epoch}, "
              f"{sq.records_reemitted} records re-emitted")
        scored = store.read()
        want = np.flatnonzero(scored >= tau_sq)
        check(np.array_equal(standing_sink.indices(sz.shards), want)
              and sq.records_reemitted == want.size, "standing-emitted-set",
              f"appended shard of {scored.size}: {{A >= tau}} at tau "
              f"{tau_sq:.6g}")
        print(f"server: {stats.completed} completed, {stats.failed} failed, "
              f"{stats.records_labeled} records labeled", flush=True)

        with clock.phase("reference"):
            refs = [run_reference(n, q, k, scores, oracle)
                    for n, q, k in zip(names, batch, keys)]
        for name, sel, ref in zip(names, results, refs):
            check_query(name, sel, ref, scores, truth)

    mem = dev.memory_stats() or {}
    peak = mem.get("peak_bytes_in_use")
    print(f"peak_bytes_in_use: "
          f"{peak if peak is not None else 'not reported by this backend'}")
    print(f"compilations: {clock.compilations}, compile time "
          f"{clock.seconds:.3f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    enable_compile_cache()
    sys.exit(main())
