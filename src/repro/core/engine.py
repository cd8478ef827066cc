"""Distributed SUPG selection engine — the production query executor.

The engine is a *precomputation-cached, vectorized, sketch-driven* data
plane: all O(n) work happens once at construction, after which any number of
RT / PT / JT queries are served off cached per-shard state.

Construction (one chunked pass over the shards, ChunkPlan-driven):

  1. per-chunk `binned.chunk_sketch_stats` — the fused Pallas score_hist
     sketch (compiled on TPU, interpret-mode on CPU; jnp fallback for
     non-tile-aligned bin counts) plus the chunk's float64 raw sampling
     masses (Σ sqrt(A), Σ A) over the chunk and over each of its
     1,024-record blocks in the same pass — merged into per-shard and
     global sketches (one psum of 48 KiB on a fleet),
  2. hierarchical sampling state: the per-chunk and per-block raw masses
     are the *only* persistent per-data sampling state — O(n / 1,024)
     floats per shard (16 B a block), never per-record arrays. Per
     (scheme, kappa) the engine caches the per-shard chunk-mass CDFs (a
     chunk's defensive mass is (1-kappa)·Σraw/Z + kappa·|chunk|/n, from
     the cached sums alone); the normalizers (Z_sqrt, Z_prop, n) come from
     `binned.weight_normalizers` on the merged sketch,
  3. shard-level sampling masses for the (shard → chunk → block → record)
     draw are the per-shard sums of those chunk masses.

Every chunked walk — sketch construction, selection emission, the PT
stage-2 region draw, and query-time chunk-draw resolution — iterates the
same `data.pipeline.ChunkPlan` and runs through the engine's persistent
`pipeline.WorkerPool`: with `workers > 1` the long-lived pool drives the
spans concurrently (memmap reads, the numpy threshold_select path and the
float64 chunk reductions all release the GIL), with results written to
preassigned slots so thread count never changes any output bit. The pool
is built once per engine (thread spin-up is not paid per walk), sized to
at most `os.cpu_count()` (requesting more is oversubscription — the clamp
is logged once; `clamp_workers=False` opts out for tests that need real
thread interleaving on small machines), and released by `engine.close()`
or the engine's context manager. Sinks carry the matching thread-safety
contract (`SelectionSink` docstring).

Query execution (zero O(n) *state* per query):

  * `draw_sample`   — multinomial over cached shard masses, then an
                      inverse-CDF draw over the cached chunk-mass CDF, then
                      a search over the chunk's block-mass prefix (from the
                      cached block sums), then an exact inverse-CDF draw
                      over freshly computed weights of *only the blocks
                      hit*, each read once; block mass × within-block p
                      reproduces the defensive-mixture p(x) exactly, so the
                      m(x) factors are globally correct with O(chunk)
                      transient memory at most,
  * `score_at`      — `np.searchsorted` shard routing + per-shard fancy
                      gathers (no per-element Python loop),
  * tau estimation  — the exact sample-level estimators (Algorithms 2-5;
                      the sample is tiny, so estimation is never distributed),
  * D' restriction  — rank → conservative bin edge through the sketch
                      (superset property),
  * selection       — *streamed*, never materialized: each shard is walked
                      in fixed-size chunks through the fused
                      `kernels/threshold_select` pass (compare + count +
                      index compaction; compiled on TPU, numpy nonzero
                      reference off-TPU) and the selected indices are
                      emitted into a `data.pipeline.SelectionSink`
                      (in-memory `IndexSink` by default, memmap
                      `BitmaskStore` for out-of-core output, `CallbackSink`
                      / `SelectionStream` for service streaming). Labeled
                      positives (Algorithm 1's R1) are folded in as a
                      sink-level merge of the positives *below* tau, so
                      emission and folding stay disjoint and per-shard
                      counts are exact without dedup state.

A query over a 1e8-record memmap store therefore peaks at O(chunk) host
memory *for every method, importance-weighted included*: no full-corpus
boolean mask or per-record CDF is ever allocated, `ShardedSelection` is a
lazy view whose `total_selected` comes from per-shard counts, boolean masks
only materialize if a caller explicitly asks for them, and the PT stage-2
uniform-in-D' draw is rank-routed through the same chunked pass. The former
O(n) surface — dense per-record inverse-CDF state behind `method="is"` —
is gone: persistent sampling state is ≤ n / 1,024 entries per (shard,
scheme) and record-level draws read only the blocks they fall in,
so the `weight_schemes=()` escape hatch is no longer needed (the argument
is kept as a cache pre-warm hint).

Multi-query execution is built on *resumable query plans* and a shared
labeling channel. The bodies of `run`/`run_joint` are generators
(`_run_plan` / `_run_joint_plan`) that *yield* `OracleRequest`s wherever
the old bodies called the oracle inline, and yield a `pipeline.ChunkWalk`
for their selection-emission pass; everything between two yields is pure
compute off the cached state. A single query drives its plan through a
trivial trampoline (submit → drain → resume, walks run on the engine
pool). `SelectionEngine.session()` returns a `QuerySession` scheduling N
plans concurrently with *double-buffered rounds*: in-flight plans are
split into two cohorts, A and B, and the scheduler alternates turns —
while cohort A's coalesced oracle drain is in flight on the channel's
dedicated drain thread (`BatchingOracle.drain_async`), cohort B's pure
plan steps (sampling, tau estimation, emission, `_uniform_in_region`
walks) already run on the engine's worker pool::

    driver   | step A₀ | step B₀ | step A₁ | step B₁ | step A₂ | ...
    channel  |         |·drain A₀·|·drain B₀·|·drain A₁·|·drain B₁·|

so oracle I/O and compute overlap instead of strictly alternating — the
"expensive predicate is the scarce resource, everything else must overlap
it" posture of the paper's rate-limited oracle model. All `ChunkWalk`s a
cohort yields in one turn are fused into a single span list
(`ChunkPlan.fuse`): eight concurrent queries' emission passes touch each
shard chunk once, not eight times. At most one drain is ever in flight,
a cohort is only stepped after its previous drain's tickets resolved, and
the scheduler commits round state before any channel call — so results
(tau / counts / sink contents) stay bit-for-bit equal to the sequential
path at any worker count and overlap depth; a pure oracle answers
identically regardless of batching, and only the per-query `oracle_calls`
*attribution* can shift with concurrency. The session coalesces the
expensive oracle across queries — one `fn` micro-batch can serve every
in-flight query — while per-query `BudgetLedger` views keep ORACLE LIMIT
enforcement per query (see `core/oracle.py` for the shared-cache budget
semantics). Per-session overlap accounting lands in `SessionStats`
(drain in-flight time vs driver wait time, fused vs raw span counts).

`run_many` is a thin wrapper over a session (`concurrency=` knob) serving a
*batch* of queries — SUPGQuery (RT/PT) and JointSUPGQuery (JT, Appendix A) —
amortizing the sketch, the cached sampling state, *and the oracle channel*
across the whole batch; this is the serving-plane entry point. Per-query
sinks make it the streaming fan-out point for a service. Because plans are
pure given (key, labels) and a pure oracle answers identically regardless
of batching, `run_many` output (tau, counts, sink contents) is bit-for-bit
identical at any `concurrency`; only the per-query `oracle_calls`
*attribution* can shift when queries overlap (the shared cache answers
later queries for free).

Shards are host-local float32 arrays: plain np.ndarray, np.memmap, or
`data.pipeline.ScoreStore` objects (consumed zero-copy through `.scores`, so
out-of-core corpora work end-to-end; sketch construction over shards larger
than `chunk_records` is itself chunked and merged, so even engine build never
materializes a full shard). On a real fleet each worker holds its shard and
the driver runs where the coordinator lives; the collective math matches
core/distributed.py.

The query path opens `jax.profiler.TraceAnnotation` spans (`supg.round`,
`supg.drain_wait`, `supg.sample[.rng|.chunk]`, `supg.bound`,
`supg.emit[.chunk|.stitch]`, `supg.append.sketch`; see
docs/architecture.md). They record only while a profiler trace runs. A
query's spans carry its request id `q`, numbered from one when
`QuerySession.submit` (or `run`) builds its plan; 0 marks work outside
any request.
"""
from __future__ import annotations

import dataclasses
import itertools
import logging
import os
import threading
import time
from typing import (Dict, Generator, List, Optional, Sequence, Tuple,
                    Union)

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.core import binned, sampling, thresholds
from repro.core.oracle import (BudgetLedger, DrainHandle, OracleClient,
                               OracleRequest, as_oracle_client)
from repro.core.queries import JointSUPGQuery, SUPGQuery
from repro.data import pipeline
from repro.kernels.threshold_select import ops as select_ops

logger = logging.getLogger(__name__)

_clamp_logged = False

# Request ids for the profiler spans: one per plan, and the id of the
# plan whose step runs on this thread (0 outside any plan step).
_request_ids = itertools.count(1)
_request = threading.local()


def _current_q() -> int:
    return getattr(_request, "q", 0)


def _effective_workers(requested: Optional[int], clamp: bool) -> int:
    """Resolve the engine's pool width. Requesting more threads than the
    machine has cores is pure oversubscription for these GIL-releasing
    numpy walks (contended cores run *slower* — see the w8 < w4 cold-build
    regression in BENCH_PR4), so the default clamps to `os.cpu_count()`
    and logs once. `clamp=False` keeps the literal request — tests that
    exercise real thread interleaving on small machines need it."""
    global _clamp_logged
    workers = max(1, int(requested)) if requested else 1
    if not clamp:
        return workers
    cpus = os.cpu_count() or 1
    if workers > cpus:
        if not _clamp_logged:
            logger.info(
                "clamping engine workers=%d to cpu_count=%d "
                "(oversubscribing GIL-releasing chunk walks is a slowdown; "
                "pass clamp_workers=False to override)", workers, cpus)
            _clamp_logged = True
        return cpus
    return workers


def _close_quietly(sink: "pipeline.SelectionSink") -> None:
    """Best-effort close on an error path: the sink must come back
    reusable (the double-open guard would otherwise wedge it), but the
    original exception owns the outcome — a close failure is secondary."""
    try:
        sink.close()
    except Exception:  # noqa: BLE001 — error path; original exc wins
        pass


class ShardedSelection:
    """Lazy view over one query's selection.

    Sink-backed (the engine's streaming output) or mask-backed (direct
    construction, kept for compatibility). In the sink-backed form nothing
    O(corpus) lives here: `total_selected` and `shard_counts` come from the
    per-shard counts the sink accumulated during emission, `indices(shard)`
    reads the sink, and `masks` materializes per-shard boolean views only
    when explicitly accessed (state-holding sinks only — a CallbackSink
    selection retains counts alone).
    """

    def __init__(self, masks: Optional[List[np.ndarray]] = None,
                 tau: float = 0.0, oracle_calls: int = 0,
                 sampled_positive_global: Optional[np.ndarray] = None,
                 sink: Optional[pipeline.SelectionSink] = None,
                 shard_sizes: Optional[Sequence[int]] = None,
                 counts: Optional[np.ndarray] = None):
        if masks is None and sink is None:
            raise ValueError("need per-shard masks or a SelectionSink")
        self.tau = float(tau)
        self.oracle_calls = int(oracle_calls)
        self.sampled_positive_global = (
            np.empty(0, np.int64) if sampled_positive_global is None
            else np.asarray(sampled_positive_global, np.int64))
        self.sink = sink
        self._masks = list(masks) if masks is not None else None
        if shard_sizes is None:
            if self._masks is not None:
                shard_sizes = [int(m.shape[0]) for m in self._masks]
            elif getattr(sink, "shard_sizes", None) is not None:
                shard_sizes = sink.shard_sizes   # an opened sink knows them
            else:
                raise ValueError(
                    "shard_sizes required when the sink has not been opened")
        self.shard_sizes = [int(n) for n in shard_sizes]
        self._counts = (None if counts is None
                        else np.asarray(counts, np.int64))

    @property
    def num_shards(self) -> int:
        """Number of score shards this selection spans."""
        return len(self.shard_sizes)

    @property
    def shard_counts(self) -> np.ndarray:
        """Per-shard selected counts (no mask materialization needed)."""
        if self._counts is not None:
            return self._counts.copy()
        return np.asarray([int(m.sum()) for m in self.masks], np.int64)

    @property
    def total_selected(self) -> int:
        """Total selected records (from counts — no mask materialization)."""
        if self._counts is not None:
            return int(self._counts.sum())
        return int(sum(int(m.sum()) for m in self.masks))

    def indices(self, shard_id: int) -> np.ndarray:
        """Sorted shard-local selected indices for one shard."""
        if self._masks is not None:
            return np.nonzero(self._masks[shard_id])[0].astype(np.int64)
        return np.asarray(self.sink.indices(shard_id), np.int64)

    @property
    def masks(self) -> List[np.ndarray]:
        """Per-shard boolean masks, materialized lazily from the sink.

        Allocates O(corpus) booleans — for large stores prefer
        `shard_counts` / `indices` / the sink itself.
        """
        if self._masks is None:
            self._masks = [self.sink.mask(i)
                           for i in range(self.num_shards)]
        return self._masks


@dataclasses.dataclass
class _ShardChunkState:
    """Cached per-shard hierarchical draw state for one (scheme, kappa):
    the shard's total defensive mass and its normalized chunk-mass CDF —
    O(n_chunks) persistent floats, never per-record arrays."""
    mass: float            # shard total defensive mass (unnormalized)
    cdf: np.ndarray        # (n_chunks,) float64 normalized chunk-mass CDF


@dataclasses.dataclass
class CorpusState:
    """One immutable corpus *epoch*: every piece of engine state an append
    replaces as a unit.

    The live plane (`repro.live`) grows the corpus by building a new
    `CorpusState` from the current one plus the appended shards and
    installing it with a single attribute assignment — old snapshots stay
    fully valid (shard arrays are never mutated, only the lists are
    extended into fresh objects), so an in-flight plan that pinned its
    epoch at the first step keeps computing against a frozen, consistent
    corpus no matter how many appends land meanwhile. Results over a
    pinned epoch are bit-for-bit what a cold engine build over exactly
    that corpus would produce.
    """

    epoch: int                          # 0 at construction, +1 per append
    shards: List[np.ndarray]            # score shards (views, never copies)
    offsets: np.ndarray                 # (n_shards+1,) int64 global offsets
    n_total: int                        # total records this epoch
    plan: pipeline.ChunkPlan            # the epoch's canonical chunk plan
    shard_sketches: List                # per-shard binned.ScoreSketch
    sketch: object                      # global merged ScoreSketch
    chunk_masses: List[sampling.ChunkMasses]   # per-shard raw chunk masses
    z: Dict[str, float]                 # global weight normalizers
    flat: Optional[np.ndarray]          # score_at gather cache (or None)
    sampling_cache: Dict[Tuple[str, float],
                         List[_ShardChunkState]] = dataclasses.field(
                             default_factory=dict)
    pins: int = 0                       # live references (engine._gc_lock)


class SelectionEngine:
    """Executes batches of SUPG queries over a list of score shards.

    Construction pays all O(n) work once (sketch + hierarchical sampling
    state, see the module docstring); queries then run off the cache.
    Use as a context manager so the engine's worker pool is released:

    >>> import numpy as np
    >>> from repro.core.queries import SUPGQuery
    >>> scores = np.linspace(0.0, 1.0, 512, dtype=np.float32)
    >>> labels = (scores > 0.75).astype(np.float32)
    >>> q = SUPGQuery(target="recall", gamma=0.9, delta=0.1,
    ...               budget=128, method="is")
    >>> with SelectionEngine([scores[:256], scores[256:]], num_bins=32,
    ...                      use_kernel=False) as eng:
    ...     sel = eng.run(None, lambda idx: labels[idx], q)
    ...     bool(0.0 <= sel.tau <= 1.0), sel.total_selected > 0
    (True, True)
    """

    def __init__(self, shards: Sequence, num_bins: int = 4096,
                 use_kernel: Optional[bool] = None,
                 weight_schemes: Sequence[str] = ("sqrt",),
                 kappa: float = sampling.DEFENSIVE_KAPPA,
                 cache_flat: Optional[bool] = None,
                 select_backend: Optional[str] = None,
                 chunk_records: Optional[int] = None,
                 workers: Optional[int] = None,
                 clamp_workers: bool = True):
        # ScoreStore (or anything exposing `.scores`) passes its memmap
        # through untouched; ndarray shards are viewed, not copied.
        raw_shards = [getattr(s, "scores", s) for s in shards]
        # Flat gather cache: for in-RAM shards a one-time concatenation
        # turns score_at into a single fancy gather. Defaults off for
        # memmap-backed (out-of-core) shards, which keep the routed path.
        # (Decide on the raw objects: np.asarray strips the memmap subclass.)
        if cache_flat is None:
            cache_flat = not any(isinstance(s, np.memmap)
                                 for s in raw_shards)
        arrs = [np.asarray(s) for s in raw_shards]
        self.num_bins = num_bins
        self.kappa = float(kappa)
        # Streaming emission knobs: chunk_records bounds per-query peak
        # memory; select_backend picks the threshold_select path (compiled
        # Pallas on TPU, numpy reference elsewhere by default — interpret
        # emulation stays available for kernel validation).
        self.chunk_records = int(chunk_records or pipeline.CHUNK_RECORDS)
        self.select_backend = (select_ops.default_backend()
                               if select_backend is None else select_backend)
        # One persistent pool per engine: thread spin-up is paid at most
        # once (lazily, on the first threaded walk), not per chunk walk.
        self.workers = _effective_workers(workers, clamp_workers)
        self.pool = pipeline.WorkerPool(self.workers)
        # Appends (the live plane's `_append_shards`) sketch under this
        # lock and publish their new CorpusState with one assignment.
        self._use_kernel = use_kernel
        self._ingest_lock = threading.Lock()
        # Epoch refcounting: `pin`/`unpin` count live references under
        # this lock; superseded epochs queue here until `gc_epochs` frees
        # the ones no plan still pins.
        self._gc_lock = threading.Lock()
        self._superseded: List[CorpusState] = []
        self.epochs_freed = 0
        plan = pipeline.ChunkPlan([int(s.shape[0]) for s in arrs],
                                  self.chunk_records)
        flat = (np.concatenate([np.asarray(s, np.float32) for s in arrs])
                if cache_flat and arrs else None)

        # 1. chunked construction pass (ChunkPlan-driven, threaded): each
        #    span yields its ScoreSketch *and* its raw sampling masses in
        #    one touch of the data. Sketches merge additively into
        #    per-shard and global sketches, so even memmap shards never
        #    materialize whole; the per-chunk and per-block masses become
        #    the persistent O(n / 1,024) hierarchical sampling state. The same
        #    pass, restricted to appended shards only, is how the live
        #    plane extends an epoch (`_append_shards`).
        shard_sketches, chunk_masses = self._sketch_shards(
            arrs, plan, 0, use_kernel)
        sketch = binned.merge_sketches(*shard_sketches)

        # 2. global weight normalizers from the merged sketch — the only
        #    cross-shard reductions sampling ever needs.
        z_sqrt, z_prop, _ = binned.weight_normalizers(sketch)

        offsets = np.concatenate(
            [[0], np.cumsum([s.shape[0] for s in arrs])]).astype(np.int64)
        self._state = CorpusState(
            epoch=0, shards=arrs, offsets=offsets,
            n_total=int(offsets[-1]), plan=plan,
            shard_sketches=shard_sketches, sketch=sketch,
            chunk_masses=chunk_masses,
            z={"sqrt": float(z_sqrt), "prop": float(z_prop)}, flat=flat)

        # 3. chunk-mass CDFs per (scheme, kappa) — O(n_chunks) each.
        #    `weight_schemes` is a pre-warm hint only: since the dense
        #    per-record CDFs are gone, every scheme is bounded-memory and
        #    un-warmed schemes build lazily on first use.
        for scheme in weight_schemes:
            self._sampling_state(scheme, self.kappa)

    def _sketch_shards(self, shards: List[np.ndarray],
                       plan: pipeline.ChunkPlan, first_shard: int,
                       use_kernel: Optional[bool]):
        """Chunked sketch + raw-mass pass over ``shards[first_shard:]``.

        Returns (per-shard sketches, per-shard ChunkMasses) for exactly
        those shards. The construction pass calls this with
        ``first_shard=0``; `_append_shards` calls it with the old shard
        count so only appended data is ever touched — and because both
        paths share this one implementation (same span order, same
        per-chunk `chunk_sketch_stats`, same merge fold), the delta path's
        per-shard results are bit-for-bit the cold build's.
        """
        spans = [sp for sp in plan if sp.shard_id >= first_shard]
        stats = self.pool.map(
            lambda sp: binned.chunk_sketch_stats(
                shards[sp.shard_id][sp.start:sp.stop], self.num_bins,
                use_kernel=use_kernel),
            spans)
        k = len(shards) - first_shard
        parts: List[List] = [[] for _ in range(k)]
        sums: List[List[Tuple]] = [[] for _ in range(k)]
        for sp, (sk, s_sqrt, s_a, b_sqrt, b_a) in zip(spans, stats):
            parts[sp.shard_id - first_shard].append(sk)
            sums[sp.shard_id - first_shard].append(
                (s_sqrt, s_a, sp.size, b_sqrt, b_a))
        # Empty shards get an all-zero sketch via the jnp path (the kernel
        # grid cannot span a zero-length operand).
        sketches = [
            binned.merge_sketches(*p) if p else
            binned.build_sketch(jnp.zeros((0,), jnp.float32), self.num_bins,
                                use_kernel=False)
            for p in parts]
        masses = [
            sampling.ChunkMasses(
                np.asarray([t[0] for t in ss], np.float64),
                np.asarray([t[1] for t in ss], np.float64),
                np.asarray([t[2] for t in ss], np.int64),
                np.concatenate([t[3] for t in ss]),
                np.concatenate([t[4] for t in ss]))
            if ss else sampling.ChunkMasses.empty()
            for ss in sums]
        return sketches, masses

    # -- lifecycle ------------------------------------------------------

    def close(self) -> None:
        """Release the engine's worker pool (joins its threads).
        Idempotent. A closed engine still serves `workers == 1` queries
        (the inline fast path owns no threads)."""
        self.pool.close()

    def __enter__(self) -> "SelectionEngine":
        return self

    def __exit__(self, *exc) -> bool:
        self.close()
        return False

    # -- cached state (epoch snapshots) ---------------------------------

    def pin(self) -> CorpusState:
        """Snapshot the current corpus epoch.

        Pass the returned `CorpusState` to `draw_sample` / `score_at` /
        `QuerySession.submit(state=...)` to keep a multi-step computation
        on one frozen, consistent corpus while `repro.live` appends land
        concurrently. Counts as a live reference: call `unpin` when the
        computation finishes so `gc_epochs` can free superseded epochs."""
        with self._gc_lock:
            st = self._state
            st.pins += 1
            return st

    def unpin(self, state: CorpusState) -> None:
        """Release a reference taken by `pin`. Unbalanced unpins raise."""
        with self._gc_lock:
            if state.pins <= 0:
                raise ValueError(
                    f"unpin of epoch {state.epoch} with no live pins")
            state.pins -= 1

    def gc_epochs(self) -> int:
        """Free superseded epochs with no live pins; returns the count.

        Frees each dead epoch's *per-epoch* host memory — the O(n) flat
        gather cache, the chunk-mass CDFs, the sketch and plan objects —
        by dropping the references. Shard arrays themselves are shared
        across epochs (appends extend the list, never copy members), so
        they stay alive exactly as long as any live epoch includes them.
        Called from `SelectionServer.snapshot()`; safe to call anytime.
        """
        with self._gc_lock:
            live = [st for st in self._superseded if st.pins > 0]
            dead = [st for st in self._superseded if st.pins <= 0]
            self._superseded = live
            self.epochs_freed += len(dead)
        for st in dead:
            st.shards = []
            st.shard_sketches = []
            st.chunk_masses = []
            st.sampling_cache = {}
            st.sketch = None
            st.flat = None
            st.plan = None
        return len(dead)

    @property
    def epochs_live(self) -> int:
        """Epochs still holding host memory: current + unfreed superseded."""
        with self._gc_lock:
            return 1 + len(self._superseded)

    @property
    def epoch(self) -> int:
        """Current corpus epoch: 0 at construction, +1 per append."""
        return self._state.epoch

    @property
    def shards(self) -> List[np.ndarray]:
        """Score shards of the current epoch (views, never copies)."""
        return self._state.shards

    @property
    def offsets(self) -> np.ndarray:
        """(n_shards+1,) int64 global record offsets, current epoch."""
        return self._state.offsets

    @property
    def n_total(self) -> int:
        """Total records in the current epoch."""
        return self._state.n_total

    @property
    def plan(self) -> pipeline.ChunkPlan:
        """The current epoch's canonical ChunkPlan."""
        return self._state.plan

    @property
    def sketch(self):
        """Global merged ScoreSketch of the current epoch."""
        return self._state.sketch

    @property
    def shard_sketches(self) -> List:
        """Per-shard ScoreSketches of the current epoch."""
        return self._state.shard_sketches

    @property
    def _chunk_masses(self) -> List[sampling.ChunkMasses]:
        return self._state.chunk_masses

    @property
    def _z(self) -> Dict[str, float]:
        return self._state.z

    @property
    def _flat(self) -> Optional[np.ndarray]:
        return self._state.flat

    @property
    def _sampling_cache(self) -> Dict[Tuple[str, float],
                                      List[_ShardChunkState]]:
        return self._state.sampling_cache

    def _append_shards(self, shards: Sequence,
                       use_kernel: Optional[bool] = None) -> CorpusState:
        """Extend the corpus by `shards`, delta-updating engine state.

        The incremental-ingestion core (`repro.live.IngestPlane` is the
        public face): sketch *only* the appended shards via the shared
        `_sketch_shards` pass, fold them into the global sketch
        (`merge_sketches` is a left fold starting at 0, so folding the new
        per-shard sketches onto the old global reproduces the cold fold
        bit-for-bit), refresh the normalizers, rebuild the O(n_chunks)
        per-(scheme, kappa) CDFs for every cached scheme (Z and n change
        on every append, but the rebuild reads only cached chunk masses —
        no old data is re-walked), and install the new `CorpusState`
        atomically. Existing epochs pinned by in-flight plans stay valid.
        Returns the new state.
        """
        raw_new = [getattr(s, "scores", s) for s in shards]
        arrs = [np.asarray(s) for s in raw_new]
        kernel = self._use_kernel if use_kernel is None else use_kernel
        with self._ingest_lock:
            st = self._state
            all_shards = st.shards + arrs
            sizes = [int(s.shape[0]) for s in all_shards]
            plan = pipeline.ChunkPlan(sizes, self.chunk_records)
            with TraceAnnotation("supg.append.sketch",
                                 records=sum(sizes[len(st.shards):])):
                new_sketches, new_masses = self._sketch_shards(
                    all_shards, plan, len(st.shards), kernel)
            sketch = (binned.merge_sketches(st.sketch, *new_sketches)
                      if new_sketches else st.sketch)
            z_sqrt, z_prop, _ = binned.weight_normalizers(sketch)
            offsets = np.concatenate(
                [[0], np.cumsum(sizes)]).astype(np.int64)
            if st.flat is None or any(isinstance(s, np.memmap)
                                      for s in raw_new):
                flat = None     # out-of-core data keeps the routed path
            elif arrs:
                flat = np.concatenate(
                    [st.flat] + [np.asarray(a, np.float32) for a in arrs])
            else:
                flat = st.flat
            new_state = CorpusState(
                epoch=st.epoch + 1, shards=all_shards, offsets=offsets,
                n_total=int(offsets[-1]), plan=plan,
                shard_sketches=st.shard_sketches + new_sketches,
                sketch=sketch, chunk_masses=st.chunk_masses + new_masses,
                z={"sqrt": float(z_sqrt), "prop": float(z_prop)},
                flat=flat)
            # Pre-warm every (scheme, kappa) the outgoing epoch served so
            # the first post-append query pays no lazy build.
            for scheme, kappa in list(st.sampling_cache):
                self._sampling_state(scheme, kappa, state=new_state)
            # Install under the GC lock so pin() never races the swap,
            # and queue the outgoing epoch for gc_epochs().
            with self._gc_lock:
                self._superseded.append(st)
                self._state = new_state
            return new_state

    def _sampling_state(self, scheme: str, kappa: float,
                        state: Optional[CorpusState] = None) \
            -> List[_ShardChunkState]:
        st = self._state if state is None else state
        cache_key = (scheme, float(kappa))
        if cache_key not in st.sampling_cache:
            states = []
            for cm in st.chunk_masses:
                if cm.sizes.size == 0:   # empty shard: zero mass, no draws
                    states.append(_ShardChunkState(
                        mass=0.0, cdf=np.empty(0, np.float64)))
                    continue
                total, cdf = sampling.chunk_mass_cdf(
                    cm.raw(scheme), cm.sizes, st.z[scheme], kappa,
                    st.n_total)
                states.append(_ShardChunkState(mass=total, cdf=cdf))
            st.sampling_cache[cache_key] = states
        return st.sampling_cache[cache_key]

    def _shard_masses(self, scheme: str, kappa: float,
                      state: Optional[CorpusState] = None) -> np.ndarray:
        states = self._sampling_state(scheme, kappa, state=state)
        mass = np.asarray([st.mass for st in states], np.float64)
        return mass / mass.sum()

    # -- sampling -------------------------------------------------------

    @staticmethod
    def _group_sorted(values: np.ndarray, order: np.ndarray):
        """Split `order` (an argsort of `values`) into runs of equal value.

        Yields (value, positions) — the argsort-grouping trick `score_at`
        uses, so grouping s draws over k groups costs one sort instead of
        k boolean mask scans.
        """
        if order.size == 0:
            return
        sorted_vals = values[order]
        cuts = np.flatnonzero(np.diff(sorted_vals)) + 1
        for grp in np.split(order, cuts):
            yield int(values[grp[0]]), grp

    def draw_sample(self, key, s: int, scheme: str = "sqrt",
                    kappa: Optional[float] = None,
                    state: Optional[CorpusState] = None):
        """Global with-replacement draws; returns (global_idx, m).

        Hierarchical (shard → chunk → block → record): multinomial over
        cached shard masses, inverse-CDF over each shard's cached chunk-
        mass CDF, then, per allocated chunk, `sampling.draw_in_blocks`:
        a search over the chunk's block-mass prefix (cached raw block sums)
        and an exact inverse-CDF draw over freshly computed p(x) of each
        distinct 1,024-record block hit — so a draw reads about a block,
        not a chunk, and no record is read twice. Transient memory is
        O(chunk) at most; persistent state O(n / 1,024). The joint draw
        probability telescopes to the global defensive-mixed p(x) (shard
        mass = Σ chunk masses, chunk mass = Σ block masses, block mass =
        Σ p(x) over the block), so m(x) = (1/n) / p(x) is globally
        correct. Draws are grouped by shard and chunk with argsorts (no
        per-shard mask scans) and chunk resolution runs through the worker
        pool; outputs land in preassigned slots, so results are identical
        at any worker count. The spans count the work: each
        `supg.sample.chunk` its distinct `blocks` and the `records` whose
        p(x) it computed, `supg.sample` the query's `records`. `state`
        pins a specific corpus epoch (default: current).
        """
        st = self._state if state is None else state
        q = _current_q()
        with TraceAnnotation("supg.sample", q=q, draws=int(s)) as trace:
            if scheme == "uniform":
                with TraceAnnotation("supg.sample.rng", q=q):
                    idx = np.asarray(
                        jax.random.randint(key, (s,), 0, st.n_total),
                        np.int64)
                trace.set_metadata(chunks=0, records=0)
                return idx, np.ones(s, np.float32)
            kappa = self.kappa if kappa is None else kappa
            states = self._sampling_state(scheme, kappa, state=st)
            mass = self._shard_masses(scheme, kappa, state=st)
            with TraceAnnotation("supg.sample.rng", q=q):
                k_alloc, k_chunk, k_rec = jax.random.split(key, 3)
                alloc = np.asarray(jax.random.categorical(
                    k_alloc, jnp.log(jnp.asarray(mass, jnp.float32)),
                    shape=(s,)))
                u_chunk = np.asarray(jax.random.uniform(k_chunk, (s,)),
                                     np.float64)
                u_rec = np.asarray(jax.random.uniform(k_rec, (s,)),
                                   np.float64)
            out_idx = np.empty(s, np.int64)
            out_m = np.empty(s, np.float32)
            work = []    # (shard_id, chunk_id, draw positions into [0, s))
            for sh, seg in self._group_sorted(
                    alloc, np.argsort(alloc, kind="stable")):
                chunk_ids = sampling.draw_from_cdf(states[sh].cdf,
                                                   u_chunk[seg])
                for ci, grp in self._group_sorted(
                        chunk_ids, np.argsort(chunk_ids, kind="stable")):
                    work.append((sh, ci, seg[grp]))

            chunk = st.plan.chunk_records

            def resolve(item):
                sh, ci, pos = item
                start = ci * chunk
                with TraceAnnotation("supg.sample.chunk", q=q,
                                     shard=int(sh), chunk=int(ci),
                                     draws=int(pos.size)) as span:
                    got = sampling.draw_in_blocks(
                        st.shards[sh][start:start + chunk],
                        st.chunk_masses[sh].block_raw(scheme, ci),
                        u_rec[pos], scheme, st.z[scheme], kappa, st.n_total)
                    span.set_metadata(blocks=got.blocks, records=got.records)
                    out_idx[pos] = st.offsets[sh] + start + got.local
                    out_m[pos] = (1.0 / st.n_total) / np.maximum(got.p,
                                                                 1e-38)
                return got.records

            records = sum(self.pool.map(resolve, work))
            trace.set_metadata(chunks=len(work), records=records)
            return out_idx, out_m

    def score_at(self, global_idx,
                 state: Optional[CorpusState] = None) -> np.ndarray:
        """Vectorized gather: one flat fancy gather when the concatenation
        cache is live, else searchsorted shard routing + per-shard fancy
        indexing (works unchanged on memmap shards). `state` pins a
        specific corpus epoch (default: current)."""
        st = self._state if state is None else state
        gi = np.asarray(global_idx, np.int64)
        if st.flat is not None:
            return st.flat[gi]
        sh = np.searchsorted(st.offsets, gi, side="right") - 1
        local = gi - st.offsets[sh]
        out = np.empty(gi.shape[0], np.float32)
        # Group draws by shard with one argsort, then gather each shard's
        # segment with a single fancy index (one touch per shard).
        order = np.argsort(sh, kind="stable")
        seg_bounds = np.searchsorted(sh[order],
                                     np.arange(len(st.shards) + 1))
        for shard_id in range(len(st.shards)):
            seg = order[seg_bounds[shard_id]:seg_bounds[shard_id + 1]]
            if seg.size:
                out[seg] = np.asarray(
                    st.shards[shard_id][local[seg]], np.float32)
        return out

    # -- query plans ------------------------------------------------------

    def _run_plan(self, key, query: SUPGQuery, *,
                  sink: Optional[pipeline.SelectionSink] = None,
                  chunk_records: Optional[int] = None,
                  ledger_parent: Optional[BudgetLedger] = None,
                  state: Optional[CorpusState] = None) \
            -> Generator[object, Optional[np.ndarray], ShardedSelection]:
        """Resumable plan for one RT/PT query.

        Yields `OracleRequest`s wherever the old body called the oracle
        inline and receives the label array back at the same point, and
        yields one `pipeline.ChunkWalk` for the selection-emission pass
        (resumed with None once its spans have run — a scheduler fuses
        all in-flight plans' walks into one pass; `_drive_plan` runs it
        directly). Everything between yields is pure compute off the
        cached state, so a scheduler may interleave any number of plans
        and answer their requests from one coalesced labeling channel.
        `ledger_parent` chains the query's budget ledger under a coarser
        shared ledger (the serving plane's per-tenant quota) — see
        `core.oracle.BudgetLedger`. The plan pins one `CorpusState` at
        its first step (`state` overrides which) and computes against
        that frozen epoch end to end, so live-plane appends landing
        mid-plan can never mix corpora. A plan that pins for itself
        unpins on exit (normal return, error, or abandonment) so
        `gc_epochs` can free the epoch; a caller passing `state=` owns
        that pin. Returns the ShardedSelection via StopIteration.value.
        """
        st = self.pin() if state is None else state
        try:
            result = yield from self._run_plan_pinned(
                key, query, sink=sink, chunk_records=chunk_records,
                ledger_parent=ledger_parent, st=st)
            return result
        finally:
            if state is None:
                self.unpin(st)

    def _run_plan_pinned(self, key, query: SUPGQuery, *,
                         sink: Optional[pipeline.SelectionSink] = None,
                         chunk_records: Optional[int] = None,
                         ledger_parent: Optional[BudgetLedger] = None,
                         st: CorpusState) \
            -> Generator[object, Optional[np.ndarray], ShardedSelection]:
        key = jax.random.PRNGKey(0) if key is None else key
        ledger = BudgetLedger(query.budget, parent=ledger_parent)
        s = query.budget
        if query.target == "recall":
            scheme = {"is": query.weight_scheme, "uniform": "uniform",
                      "noci": "uniform"}[query.method]
            idx, m = self.draw_sample(key, s, scheme, state=st)
            o_s = yield OracleRequest(idx, ledger)
            with TraceAnnotation("supg.bound", q=_current_q()):
                a_s = self.score_at(idx, state=st)
                if query.method == "noci":
                    res = thresholds.tau_unoci_r(a_s, o_s, query.gamma)
                else:
                    res = thresholds.tau_ci_r(a_s, o_s, m, query.gamma,
                                              query.delta)
                tau = float(res.tau)
        else:
            k0, k1 = jax.random.split(key)
            if query.method == "is" and query.two_stage:
                idx0, m0 = self.draw_sample(k0, s // 2,
                                            query.weight_scheme, state=st)
                o0 = yield OracleRequest(idx0, ledger)
                _, rank = thresholds.pt_stage1_nmatch(
                    o0, m0, st.n_total, query.gamma, query.delta)
                tau_dp = float(binned.rank_to_threshold(st.sketch,
                                                        int(rank)))
                # stage 2: uniform on D' via per-shard masked draws
                idx1 = self._uniform_in_region(k1, s - s // 2, tau_dp,
                                               state=st)
                o1 = yield OracleRequest(idx1, ledger)
                with TraceAnnotation("supg.bound", q=_current_q()):
                    a1 = self.score_at(idx1, state=st)
                    res = thresholds.tau_ci_p(a1, o1, query.gamma,
                                              query.delta / 2.0,
                                              min_step=query.min_step)
                    tau = float(res.tau)
            else:
                scheme = ("uniform" if query.method in ("uniform", "noci")
                          else query.weight_scheme)
                idx, m = self.draw_sample(k0, s, scheme, state=st)
                o_s = yield OracleRequest(idx, ledger)
                with TraceAnnotation("supg.bound", q=_current_q()):
                    a_s = self.score_at(idx, state=st)
                    if query.method == "noci":
                        res = thresholds.tau_unoci_p(a_s, o_s, query.gamma)
                    else:
                        res = thresholds.tau_ci_p(
                            a_s, o_s, query.gamma, query.delta,
                            m_s=None if scheme == "uniform" else m,
                            min_step=query.min_step)
                    tau = float(res.tau)

        pos = ledger.labeled_positives()
        walk, out_sink, finish = self._emission_walk(tau, pos, sink,
                                                     chunk_records,
                                                     state=st)
        try:
            yield walk
        except BaseException:
            # Emission died (a CallbackSink consumer raised, the walk was
            # poisoned, or the plan was abandoned at this yield): release
            # the sink so sequential reuse still works.
            _close_quietly(out_sink)
            raise
        return finish(ledger.charged)

    def _run_joint_plan(self, key, query: JointSUPGQuery, *,
                        sink: Optional[pipeline.SelectionSink] = None,
                        chunk_records: Optional[int] = None,
                        ledger_parent: Optional[BudgetLedger] = None,
                        state: Optional[CorpusState] = None) \
            -> Generator[object, Optional[np.ndarray], ShardedSelection]:
        """Resumable plan for one JT query (Appendix A): the RT sub-plan
        (delegated via `yield from`, so its oracle requests ride the same
        channel), then chunked verification requests over the candidate
        set. The verification ledger is capped at n_total — unbounded by
        design — and exists for `oracle_calls` attribution; under a
        `ledger_parent` (tenant quota) verification labels are metered
        against the parent too, so a quota-capped JT query fails loudly
        instead of labeling past its tenant's allowance. One pinned
        `CorpusState` spans both stages (unpinned on exit when this plan
        took the pin; a caller passing `state=` owns theirs)."""
        st = self.pin() if state is None else state
        try:
            result = yield from self._run_joint_plan_pinned(
                key, query, sink=sink, chunk_records=chunk_records,
                ledger_parent=ledger_parent, st=st)
            return result
        finally:
            if state is None:
                self.unpin(st)

    def _run_joint_plan_pinned(self, key, query: JointSUPGQuery, *,
                               sink=None, chunk_records=None,
                               ledger_parent=None, st: CorpusState) \
            -> Generator[object, Optional[np.ndarray], ShardedSelection]:
        rt = SUPGQuery(target="recall", gamma=query.gamma_recall,
                       delta=query.delta, budget=query.stage_budget,
                       method=query.method)
        cand = yield from self._run_plan(key, rt,
                                         chunk_records=chunk_records,
                                         ledger_parent=ledger_parent,
                                         state=st)
        vledger = BudgetLedger(st.n_total, parent=ledger_parent)
        out = pipeline.IndexSink() if sink is None else sink
        chunk = int(chunk_records or self.chunk_records)
        sizes = [int(s.shape[0]) for s in st.shards]
        out.open(sizes)
        try:
            for sh in range(len(st.shards)):
                local = cand.indices(sh)
                for start in range(0, local.size, chunk):
                    seg = local[start:start + chunk]
                    labels = yield OracleRequest(st.offsets[sh] + seg,
                                                 vledger)
                    out.emit(sh, seg[labels > 0.5])
        except BaseException:
            # Failed (or abandoned — GeneratorExit) mid-verification:
            # release the sink so sequential reuse still works; its
            # partial contents are owned by the raised error.
            _close_quietly(out)
            raise
        counts = out.close()
        return ShardedSelection(
            tau=cand.tau,
            oracle_calls=cand.oracle_calls + vledger.charged,
            sampled_positive_global=cand.sampled_positive_global,
            sink=out, shard_sizes=sizes, counts=counts)

    def _plan_for(self, key, query, *, sink=None, chunk_records=None,
                  ledger_parent=None, state=None):
        if isinstance(query, JointSUPGQuery):
            return self._run_joint_plan(key, query, sink=sink,
                                        chunk_records=chunk_records,
                                        ledger_parent=ledger_parent,
                                        state=state)
        return self._run_plan(key, query, sink=sink,
                              chunk_records=chunk_records,
                              ledger_parent=ledger_parent, state=state)

    # -- query entry points -----------------------------------------------

    def run(self, key, oracle_fn, query: SUPGQuery, *,
            sink: Optional[pipeline.SelectionSink] = None,
            chunk_records: Optional[int] = None) -> ShardedSelection:
        """Execute one RT/PT query, streaming the selection through `sink`.

        `oracle_fn` is a plain ``indices -> labels`` callable (adapted
        into a private labeling channel — exactly the historical
        per-query-budget semantics) or an `OracleClient` such as a shared
        `BatchingOracle`, in which case its label cache carries over.
        With no sink the selection lands in an in-memory `IndexSink`
        (O(selected) host memory); pass a `BitmaskStore` for out-of-core
        output or a `CallbackSink` to consume chunks as they are emitted.
        """
        return _drive_plan(
            self._run_plan(key, query, sink=sink,
                           chunk_records=chunk_records),
            as_oracle_client(oracle_fn), self.pool)

    def run_joint(self, key, oracle_fn, query: JointSUPGQuery, *,
                  sink: Optional[pipeline.SelectionSink] = None,
                  chunk_records: Optional[int] = None) -> ShardedSelection:
        """Engine-level JT query (Appendix A): RT stage at gamma_recall,
        then exhaustive oracle filtering of the candidate set. The RT stage
        streams into an internal IndexSink; verification then re-walks the
        candidate indices in chunks, emitting only oracle-verified positives
        into `sink` (precision exactly 1.0; oracle usage beyond the RT
        stage is unbounded by design). Both stages ride one labeling
        channel, so verification gets RT-stage labels from the cache for
        free."""
        return _drive_plan(
            self._run_joint_plan(key, query, sink=sink,
                                 chunk_records=chunk_records),
            as_oracle_client(oracle_fn), self.pool)

    def session(self, oracle_fn, *, concurrency: Optional[int] = None,
                max_batch: Optional[int] = None,
                retry=None, call_timeout_s: Optional[float] = None,
                breaker=None) -> "QuerySession":
        """Open a `QuerySession`: the multi-query scheduler + shared
        batched-oracle channel. Use as a context manager::

            with engine.session(oracle_fn, concurrency=8) as sess:
                handles = [sess.submit(q, key=k) for q, k in work]
                results = [h.result() for h in handles]

        All in-flight plans' oracle requests funnel through one
        `BatchingOracle` (unless `oracle_fn` is already an `OracleClient`,
        which is then shared as-is), so overlapping samples are labeled
        once and micro-batches span queries. Scheduling is double-buffered
        (see the module docstring): one cohort's coalesced drain runs on
        the channel's drain thread while the other cohort's plan steps run
        on the engine's worker pool, and all of a round's emission walks
        fuse into one chunk pass. `concurrency` caps in-flight plans
        (default: unbounded — every submitted query joins the next round);
        `max_batch` caps records per underlying oracle call. Overlap
        accounting is on `session.stats` (a `SessionStats`).

        `retry` (a `core.resilience.RetryPolicy`), `call_timeout_s`, and
        `breaker` (a `core.resilience.CircuitBreaker`) configure the
        private channel's fault tolerance when `oracle_fn` is a bare
        callable — failed micro-batches are retried per policy, and a
        query whose records exhaust retries fails alone while co-batched
        queries complete. Retry accounting lands on `session.stats`.
        """
        return QuerySession(self, oracle_fn, concurrency=concurrency,
                            max_batch=max_batch, retry=retry,
                            call_timeout_s=call_timeout_s, breaker=breaker)

    def run_many(self, key, oracle_fn,
                 queries: Sequence[Union[SUPGQuery, JointSUPGQuery]], *,
                 sinks: Optional[Sequence[
                     Optional[pipeline.SelectionSink]]] = None,
                 chunk_records: Optional[int] = None,
                 concurrency: Optional[int] = None) \
            -> List[ShardedSelection]:
        """Serve a batch of RT / PT / JT queries off one cached state —
        a thin wrapper over `session()`.

        The sketch, shard masses, and per-scheme CDFs were built once at
        construction; each query only pays O(s) sampling + one streamed
        O(n) emission pass, and the whole batch shares one labeling
        channel (overlapping samples are labeled once; oracle calls are
        coalesced across queries into micro-batches). Budgets are enforced
        per query via `BudgetLedger` views. `concurrency` caps in-flight
        plans (default: the whole batch); output (tau, counts, sink
        contents) is bit-for-bit identical at any concurrency for a pure
        oracle. `sinks`, when given, supplies one sink per query (None
        entries fall back to a fresh IndexSink) — the streaming fan-out
        point for a service; one sink object cannot serve two queries
        (their emissions would interleave).
        """
        if sinks is None:
            sinks = [None] * len(queries)
        # Validate the sink list before any key splitting so a malformed
        # call fails on the actual mistake, not a shape error downstream.
        if len(sinks) != len(queries):
            raise ValueError(
                f"need exactly one sink (or None) per query: got "
                f"{len(sinks)} sinks for {len(queries)} queries")
        live = [id(s) for s in sinks if s is not None]
        if len(live) != len(set(live)):
            raise ValueError(
                "one sink object is shared by multiple queries; their "
                "emissions would interleave — give each query its own sink")
        if not len(queries):
            return []
        keys = jax.random.split(
            jax.random.PRNGKey(0) if key is None else key, len(queries))
        with self.session(oracle_fn, concurrency=concurrency) as sess:
            handles = [sess.submit(q, key=k, sink=snk,
                                   chunk_records=chunk_records)
                       for k, q, snk in zip(keys, queries, sinks)]
            return [h.result() for h in handles]

    # -- streaming emission ---------------------------------------------

    def _emission_walk(self, tau: float, pos: np.ndarray,
                       sink: Optional[pipeline.SelectionSink],
                       chunk_records: Optional[int],
                       state: Optional[CorpusState] = None,
                       shard_ids: Optional[Sequence[int]] = None):
        """Prepare the streamed {A >= tau} ∪ labeled-positives emission.

        Opens the sink, folds the labeled positives *below* tau (those
        at/above tau stream out of their own chunks — fold/emit stay
        disjoint and counts exact), and returns ``(walk, sink, finish)``:
        the `ChunkWalk` whose spans run the fused threshold_select pass,
        the opened sink, and the closure that closes the sink and builds
        the `ShardedSelection` once every span has run. Splitting the walk
        from its bookkeeping is what lets a `QuerySession` fuse all
        in-flight plans' emission passes into one span list per round.
        The sink serializes its own consumption (see its thread-safety
        contract), peak host memory is O(chunk), and no full-corpus
        boolean mask is ever allocated. Unscored records (the -1 sentinel)
        are never emitted by the threshold pass; an unscored labeled
        positive still folds in, exactly like the materialized path
        selected it. If the fold itself dies (e.g. a CallbackSink consumer
        raised) the sink is released before the error propagates.

        `state` pins the corpus epoch walked; `shard_ids` restricts the
        walk to those shards only (the live plane's standing re-emission
        over appended shards — the sink still opens with the epoch's full
        shard sizes, so global offsets stay correct).
        """
        st = self._state if state is None else state
        sink = pipeline.IndexSink() if sink is None else sink
        chunk = int(chunk_records or self.chunk_records)
        sizes = [int(s.shape[0]) for s in st.shards]
        if shard_ids is not None:
            plan = pipeline.ChunkPlan(sizes, chunk, shard_ids=shard_ids)
        else:
            plan = (st.plan if chunk == self.chunk_records
                    else pipeline.ChunkPlan(sizes, chunk))
        sink.open(sizes)
        try:
            if pos.size:
                below = pos[self.score_at(pos, state=st) < tau]
                if below.size:
                    sh_ids = np.searchsorted(st.offsets, below,
                                             side="right") - 1
                    for shard_id in np.unique(sh_ids):
                        loc = (below[sh_ids == shard_id]
                               - st.offsets[shard_id])
                        sink.fold(int(shard_id), np.unique(loc))
        except BaseException:
            _close_quietly(sink)
            raise

        def emit_span(span):
            with TraceAnnotation("supg.emit.chunk", shard=span.shard_id,
                                 chunk=span.chunk_id) as trace:
                block = st.shards[span.shard_id][span.start:span.stop]
                local = select_ops.threshold_select(
                    block, tau, backend=self.select_backend)
                if local.size:
                    with TraceAnnotation("supg.emit.stitch"):
                        sink.emit(span.shard_id, span.start + local)
                trace.set_metadata(selected=int(local.size))

        def finish(oracle_calls: int) -> ShardedSelection:
            counts = sink.close()
            return ShardedSelection(
                tau=float(tau), oracle_calls=oracle_calls,
                sampled_positive_global=pos, sink=sink,
                shard_sizes=sizes, counts=counts)

        return pipeline.ChunkWalk(plan, emit_span), sink, finish

    def _emit_selection(self, tau: float, pos: np.ndarray,
                        oracle_calls: int,
                        sink: Optional[pipeline.SelectionSink],
                        chunk_records: Optional[int],
                        state: Optional[CorpusState] = None) \
            -> ShardedSelection:
        """Synchronous emission: `_emission_walk` run to completion on the
        engine's pool — the non-scheduled path (and benches)."""
        walk, out_sink, finish = self._emission_walk(tau, pos, sink,
                                                     chunk_records,
                                                     state=state)
        err = _run_walks([walk], self.pool, _current_q())[0]
        if err is not None:
            # Emission died (e.g. a CallbackSink consumer raised): release
            # the sink so sequential reuse still works.
            _close_quietly(out_sink)
            raise err
        return finish(oracle_calls)

    def _uniform_in_region(self, key, s, tau, state=None):
        """Uniform draws from {A >= tau} across shards, chunk-streamed.

        One ChunkPlan counting pass (threaded over spans) yields per-chunk
        region sizes; draws are then rank-routed through those cached
        counts, so the resolution pass re-runs threshold_select only on
        chunks that actually received draws — chunks whose region is empty
        carry zero rank mass and are skipped for free. The PT stage-2
        restriction therefore runs at O(chunk) peak memory like selection
        emission: no full-shard mask or nonzero is ever materialized
        (unscored sentinel records are excluded, like emission).

        Shards whose region is empty get exactly zero categorical mass (no
        floor), so draws can never be clamped onto records below tau. If the
        region is globally empty the draws fall back to uniform over all
        records — tau estimation then sees an unrestricted uniform sample,
        which keeps the estimator valid (D' restriction is an efficiency
        device, never a correctness requirement).
        """
        st = self._state if state is None else state
        q = _current_q()
        with TraceAnnotation("supg.sample", q=q, draws=int(s)) as trace:
            plan = st.plan
            spans = list(plan)

            def count_span(span):
                # Count through the exact same selection pass the resolve
                # step uses: any dtype/backend rounding disagreement between
                # the two would desynchronize ranks from region sizes.
                return select_ops.threshold_select(
                    st.shards[span.shard_id][span.start:span.stop], tau,
                    backend=self.select_backend).size

            span_counts = self.pool.map(count_span, spans)
            per_shard = [np.zeros(plan.num_chunks(sh), np.int64)
                         for sh in range(len(st.shards))]
            for span, c in zip(spans, span_counts):
                per_shard[span.shard_id][span.chunk_id] = c
            counts = np.asarray([pc.sum() for pc in per_shard], np.float64)
            total = counts.sum()
            if total == 0:
                with TraceAnnotation("supg.sample.rng", q=q):
                    idx = np.asarray(jax.random.randint(
                        key, (s,), 0, st.n_total), np.int64)
                trace.set_metadata(chunks=0)
                return idx
            mass = counts / total
            with TraceAnnotation("supg.sample.rng", q=q):
                k_alloc, k_draw = jax.random.split(key)
                # log(0) = -inf => empty shards are excluded from the
                # categorical.
                alloc = np.asarray(jax.random.categorical(
                    k_alloc, jnp.log(jnp.asarray(mass, jnp.float32)),
                    shape=(s,)))
                dkeys = jax.random.split(k_draw, len(st.shards))
            out = np.empty(s, np.int64)
            work = []  # (shard_id, chunk_id, positions, in-chunk region ranks)
            for sh, seg in self._group_sorted(
                    alloc, np.argsort(alloc, kind="stable")):
                cum = np.concatenate([[0], np.cumsum(per_shard[sh])])
                # uniform region ranks, then rank -> (chunk, offset in
                # chunk); only chunks with nonzero region counts can be hit.
                with TraceAnnotation("supg.sample.rng", q=q):
                    r = np.asarray(jax.random.randint(
                        dkeys[sh], (seg.size,), 0, int(cum[-1])), np.int64)
                ch = np.searchsorted(cum, r, side="right") - 1
                corder = np.argsort(ch, kind="stable")
                for ci, grp in self._group_sorted(ch, corder):
                    work.append((sh, ci, seg[grp], r[grp] - cum[ci]))

            chunk = plan.chunk_records

            def resolve(item):
                sh, ci, pos, ranks = item
                start = ci * chunk
                with TraceAnnotation("supg.sample.chunk", q=q,
                                     shard=int(sh), chunk=int(ci),
                                     draws=int(pos.size)):
                    region = select_ops.threshold_select(
                        st.shards[sh][start:start + chunk], tau,
                        backend=self.select_backend)
                    out[pos] = st.offsets[sh] + start + region[ranks]

            self.pool.map(resolve, work)
            trace.set_metadata(chunks=len(work))
            return out


# ---------------------------------------------------------------------------
# Query scheduling — the async multi-query execution plane
# ---------------------------------------------------------------------------

def _drive_plan(plan, client: OracleClient,
                pool: Optional[pipeline.WorkerPool] = None) \
        -> ShardedSelection:
    """Sequential trampoline: advance one plan to each yield point —
    `OracleRequest`s are answered through the channel (submit + result,
    which drains), `ChunkWalk`s run to completion on the engine pool —
    then resume. This is exactly the single-query execution path of
    `run`/`run_joint`.

    A channel or walk error is thrown *into* the plan at its yield point,
    not raised from here directly: the suspended generator would otherwise
    stay alive on the exception's traceback with its cleanup (sink
    release) never run."""
    q = next(_request_ids)
    outer, _request.q = _current_q(), q
    try:
        send = None
        while True:
            try:
                req = plan.send(send)
            except StopIteration as done:
                return done.value
            try:
                if isinstance(req, pipeline.ChunkWalk):
                    walk_err = _run_walks([req], pool, q)[0]
                    if walk_err is not None:
                        raise walk_err
                    send = None
                else:
                    send = client.submit(req.indices,
                                         ledger=req.ledger).result()
            except BaseException as err:  # noqa: BLE001 — rethrown in plan
                try:
                    plan.throw(err)       # runs the plan's except/finally
                except StopIteration as done:
                    return done.value     # plan absorbed the error
                raise RuntimeError(
                    "plan yielded again after its request failed")
    finally:
        _request.q = outer


def _fused_spans(walks: Sequence[pipeline.ChunkWalk]) -> int:
    """Chunk spans a fused pass over `walks` runs: each geometry once."""
    geoms = {w.plan.geometry: w.plan.total_chunks for w in walks}
    return sum(geoms.values())


def _run_walks(walks: Sequence[pipeline.ChunkWalk],
               pool: Optional[pipeline.WorkerPool], q: int) \
        -> List[Optional[BaseException]]:
    """`pipeline.run_fused` inside the `supg.emit` span; `q` is the first
    walk's request id."""
    with TraceAnnotation("supg.emit", q=q, walks=len(walks),
                         spans=_fused_spans(walks)):
        return pipeline.run_fused(walks, pool)


_START = object()       # inbox sentinel: plan not yet started


class QueryHandle:
    """Future for one query submitted to a `QuerySession`.

    `result()` pumps the session's scheduler until this query's plan
    completes, then returns its `ShardedSelection` — or raises the plan's
    error (`BudgetExceededError` if this query's ledger was rejected in a
    coalesced drain; other queries are unaffected).
    """

    def __init__(self, session: "QuerySession", query, sink):
        self.query = query
        self.sink = sink
        self.q = next(_request_ids)      # request id on profiler spans
        self._session = session
        self._result: Optional[ShardedSelection] = None
        self._error: Optional[BaseException] = None
        self._done = False

    @property
    def done(self) -> bool:
        """True once this query's plan has completed (or failed)."""
        return self._done

    def result(self) -> ShardedSelection:
        """This query's `ShardedSelection` (pumps the session if needed)."""
        if not self._done:
            self._session._pump(until=self)
        if self._error is not None:
            raise self._error
        return self._result


@dataclasses.dataclass
class SessionStats:
    """Per-session scheduler accounting — the observability surface the
    double-buffered overlap is judged by.

    `drain_busy_s` is total wall time coalesced drains were in flight on
    the channel; `drain_wait_s` is how long the driver actually blocked
    waiting for them. Their difference (`overlap_hidden_s`) is oracle
    latency hidden under the other cohort's compute. `walk_spans` counts
    chunk spans the round's emission walks would have cost run separately;
    `fused_spans` is what the fused pass actually walked — the gap
    (`spans_saved`) is data chunks touched once instead of k times."""

    rounds: int = 0            # scheduler turns taken
    plan_steps: int = 0        # generator resumptions
    drains: int = 0            # coalesced drains launched
    drain_busy_s: float = 0.0  # wall time drains spent in flight
    drain_wait_s: float = 0.0  # driver time blocked awaiting drains
    fused_walks: int = 0       # emission walks executed through fusion
    walk_spans: int = 0        # spans those walks would cost unfused
    fused_spans: int = 0       # spans the fused passes actually ran
    retries: int = 0           # oracle calls re-attempted (resilience)
    timeouts: int = 0          # oracle calls killed by the watchdog
    batch_failures: int = 0    # micro-batches that exhausted retries/fatal
    batch_sheds: int = 0       # micro-batches shed by the open circuit

    @property
    def overlap_hidden_s(self) -> float:
        """Oracle in-flight time the driver never blocked on."""
        return max(0.0, self.drain_busy_s - self.drain_wait_s)

    @property
    def spans_saved(self) -> int:
        """Chunk touches eliminated by per-round walk fusion."""
        return self.walk_spans - self.fused_spans


class QuerySession:
    """Scheduler that drives N query plans concurrently over one shared,
    batched labeling channel — `SelectionEngine.session()`'s return value.

    Scheduling is *double-buffered* and deterministic: in-flight plans are
    split across two cohorts that take strictly alternating turns. One
    turn advances every plan of the current cohort to its next yield
    through the engine's persistent `WorkerPool` (each step is pure
    compute — sampling, tau estimation, emission — off the engine's
    cached state; all `ChunkWalk`s the cohort yields are fused into one
    span list, so k emission passes touch each shard chunk once), then
    resolves the *other* cohort's in-flight drain, submits this cohort's
    requests in submission order, and launches their coalesced drain
    asynchronously (`BatchingOracle.drain_async`) before handing the turn
    over. The drain is therefore in flight on the channel's dedicated
    drain thread exactly while the other cohort computes. At most one
    drain is ever outstanding, a cohort is stepped only after its own
    drain's tickets resolved, and cohort state commits before any channel
    call — so results are bit-for-bit the sequential path's at any worker
    count and overlap depth, and the fixed submission order keeps charge
    attribution reproducible at a given concurrency.

    Plans that finish leave their cohort; queued plans join cohorts in
    submission order, balanced so both cohorts carry work. A plan whose
    ticket failed (e.g. `BudgetExceededError`) has the error thrown into
    it at its yield point on its next turn — that query's handle raises,
    co-batched queries are untouched; a poisoned drain reaches every
    ticket it owned, so nothing fails silently.

    The scheduler itself runs on whichever thread pumps it (a
    `handle.result()` call, a `step()` loop, or the context-manager
    exit) — the only background activity is the channel's drain thread,
    which never touches plan or engine state, so results are
    deterministic functions of (keys, queries, oracle, concurrency).

    >>> import jax, numpy as np
    >>> from repro.core.queries import SUPGQuery
    >>> scores = np.linspace(0.0, 1.0, 512, dtype=np.float32)
    >>> labels = (scores > 0.75).astype(np.float32)
    >>> qs = [SUPGQuery(target="recall", gamma=0.9, delta=0.1,
    ...                 budget=128, method="is") for _ in range(3)]
    >>> keys = jax.random.split(jax.random.PRNGKey(0), 3)
    >>> with SelectionEngine([scores], num_bins=32,
    ...                      use_kernel=False) as eng:
    ...     with eng.session(lambda idx: labels[idx]) as sess:
    ...         handles = [sess.submit(q, key=k)
    ...                    for q, k in zip(qs, keys)]
    ...         results = [h.result() for h in handles]
    >>> len(results), sess.client.fn_calls <= len(qs)  # coalesced drains
    (3, True)
    """

    def __init__(self, engine: SelectionEngine, oracle_fn, *,
                 concurrency: Optional[int] = None,
                 max_batch: Optional[int] = None,
                 retry=None, call_timeout_s: Optional[float] = None,
                 breaker=None):
        self.engine = engine
        self._owns_client = not isinstance(oracle_fn, OracleClient)
        self.client = as_oracle_client(oracle_fn, max_batch=max_batch,
                                       retry=retry,
                                       call_timeout_s=call_timeout_s,
                                       breaker=breaker)
        self.concurrency = (None if concurrency is None
                            else max(1, int(concurrency)))
        self.stats = SessionStats()
        self._queued: List[Tuple[QueryHandle, Generator]] = []
        # Two cohorts of slots [handle, plan, inbox]; _turn picks the one
        # stepped next. _outstanding is the in-flight drain of the cohort
        # whose turn just ended: (DrainHandle, [(slot, ticket), ...]).
        self._bufs: List[List[List]] = [[], []]
        self._turn = 0
        self._outstanding: Optional[
            Tuple[DrainHandle, List[Tuple[List, object]]]] = None
        self._closed = False

    # -- submission -------------------------------------------------------

    def submit(self, query, *, key=None,
               sink: Optional[pipeline.SelectionSink] = None,
               chunk_records: Optional[int] = None,
               ledger_parent: Optional[BudgetLedger] = None,
               state: Optional[CorpusState] = None) -> QueryHandle:
        """Enqueue one RT/PT/JT query; returns its `QueryHandle`.

        `key` defaults to PRNGKey(0) (pass distinct keys for distinct
        samples — `run_many` splits one key across its batch). The plan
        starts when a scheduler turn has a free cohort slot
        (`concurrency` caps the two cohorts' combined size).
        `ledger_parent` chains the query's budget ledger under a shared
        quota ledger — the serving plane passes each tenant's here.
        `state` pins the plan to a specific corpus epoch (`engine.pin()`)
        so a caller racing live-plane appends controls exactly which
        corpus the query certifies; default is the epoch current at the
        plan's first step.
        """
        if self._closed:
            raise RuntimeError("QuerySession is closed")
        handle = QueryHandle(self, query, sink)
        plan = self.engine._plan_for(key, query, sink=sink,
                                     chunk_records=chunk_records,
                                     ledger_parent=ledger_parent,
                                     state=state)
        self._queued.append((handle, plan))
        return handle

    def submit_plan(self, plan: Generator, *, query=None,
                    sink: Optional[pipeline.SelectionSink] = None) \
            -> QueryHandle:
        """Enqueue a pre-built resumable plan; returns its `QueryHandle`.

        The escape hatch for plans that are not SUPG queries but speak
        the same yield protocol (`OracleRequest` / `pipeline.ChunkWalk`):
        the live plane's standing re-emission walks enter here, joining
        the same cohorts, walk fusion, and coalesced drains as ordinary
        queries. `query`/`sink` only annotate the returned handle.
        """
        if self._closed:
            raise RuntimeError("QuerySession is closed")
        handle = QueryHandle(self, query, sink)
        self._queued.append((handle, plan))
        return handle

    def drain(self) -> None:
        """Explicit barrier on the shared channel (pending tickets only —
        plans advance when the scheduler is pumped)."""
        self.client.drain()

    # -- scheduler --------------------------------------------------------

    def _work_left(self) -> bool:
        return bool(self._queued or self._bufs[0] or self._bufs[1]
                    or self._outstanding is not None)

    @property
    def in_flight(self) -> int:
        """Queries admitted or queued but not yet completed."""
        return (len(self._queued) + len(self._bufs[0])
                + len(self._bufs[1]))

    def step(self) -> bool:
        """Advance the scheduler by exactly one turn; True if work remains.

        The incremental pump a long-lived host (the `repro.serve` plane)
        drives from its own scheduler thread: submit() any number of
        queries, call `step()` until it returns False (or poll handles'
        `done` between turns), and new submissions join the next turn's
        admission. Equivalent to the internal pumping `result()` does,
        exposed one turn at a time so a server can interleave admission,
        timeout bookkeeping, and completion delivery with plan progress.
        """
        if self._work_left():
            self._round()
        return self._work_left()

    def _pump(self, until: Optional[QueryHandle] = None) -> None:
        """Run scheduler turns until `until` (or everything) completes."""
        while not (until._done if until is not None
                   else not self._work_left()):
            if not self._work_left():
                raise RuntimeError(
                    "pumped a handle that is neither queued nor active")
            self._round()

    def _admit(self, buf: List[List]) -> None:
        """Move queued plans into `buf`, keeping the cohorts balanced:
        each cohort is filled to at most half the concurrency cap, so a
        full session always has a second cohort to compute under the
        first one's drain."""
        active = len(self._bufs[0]) + len(self._bufs[1])
        cap = self.concurrency or (active + len(self._queued))
        half = max(1, -(-cap // 2))
        while self._queued and active < cap and len(buf) < half:
            handle, plan = self._queued.pop(0)
            buf.append([handle, plan, _START])
            active += 1

    def _step_cohort(self, buf: List[List]) -> List[Tuple[str, object]]:
        """Advance every slot of one cohort to its next `OracleRequest`
        or completion. Slots pausing at `ChunkWalk` yields have their
        walks fused (`ChunkPlan.fuse`) and run as one span pass on the
        engine pool between micro-steps, then resume — so the cohort
        leaves this call holding only oracle requests and results.
        Thread count never changes outputs: steps land in their slots,
        and walk errors go back into exactly the plan that owns them."""

        def step(i):
            handle, plan, inbox = buf[i]
            outer, _request.q = _current_q(), handle.q
            try:
                if inbox is _START:
                    out = plan.send(None)
                elif isinstance(inbox, BaseException):
                    out = plan.throw(inbox)
                else:
                    out = plan.send(inbox)
            except StopIteration as done:
                return ("done", done.value)
            except BaseException as err:  # noqa: BLE001 — owned by handle
                return ("err", err)
            finally:
                _request.q = outer
            if isinstance(out, pipeline.ChunkWalk):
                return ("walk", out)
            return ("req", out)

        outcomes: List[Optional[Tuple[str, object]]] = [None] * len(buf)
        live = list(range(len(buf)))
        while live:
            self.stats.plan_steps += len(live)
            stepped = self.engine.pool.map(step, live)
            walkers: List[int] = []
            for i, res in zip(live, stepped):
                outcomes[i] = res
                if res[0] == "walk":
                    walkers.append(i)
            if not walkers:
                break
            walks = [outcomes[i][1] for i in walkers]
            self.stats.fused_walks += len(walks)
            self.stats.walk_spans += sum(
                w.plan.total_chunks for w in walks)
            self.stats.fused_spans += _fused_spans(walks)
            errs = _run_walks(walks, self.engine.pool, buf[walkers[0]][0].q)
            for i, err in zip(walkers, errs):
                # None resumes the plan past its walk; an error is thrown
                # into it (releasing its sink) on the re-step below.
                buf[i][2] = err
            live = walkers
        return outcomes

    def _await_outstanding(self) -> None:
        """Settle the in-flight drain (if any) and deliver its tickets'
        labels — or its poison — into the owning cohort's inboxes."""
        if self._outstanding is None:
            return
        handle, pending = self._outstanding
        self._outstanding = None
        t0 = time.perf_counter()
        with TraceAnnotation("supg.drain_wait", records=sum(
                int(ticket.indices.size) for _, ticket in pending)):
            handle.wait()
        self.stats.drain_wait_s += time.perf_counter() - t0
        self.stats.drain_busy_s += handle.duration_s
        self.stats.retries += handle.retries
        self.stats.timeouts += handle.timeouts
        self.stats.batch_failures += handle.batch_failures
        self.stats.batch_sheds += handle.batch_sheds
        for slot, ticket in pending:
            try:
                slot[2] = ticket.result()
            except BaseException as err:  # noqa: BLE001 — rethrown in plan
                slot[2] = err

    def _round(self) -> None:
        """One scheduler turn inside the `supg.round` span, whose
        arguments are the plan steps and fused walks it added to
        `stats`."""
        steps, walks = self.stats.plan_steps, self.stats.fused_walks
        with TraceAnnotation("supg.round") as trace:
            self._turn_once()
            trace.set_metadata(plans=self.stats.plan_steps - steps,
                               walks=self.stats.fused_walks - walks)

    def _turn_once(self) -> None:
        """Admit + step the current cohort (fusing its walks), commit,
        resolve the other cohort's drain, then launch this cohort's drain
        asynchronously and hand the turn over."""
        cur = self._turn
        buf = self._bufs[cur]
        self._admit(buf)
        self.stats.rounds += 1
        requests: List[Tuple[List, OracleRequest]] = []
        if buf:
            # This is the compute that overlaps the other cohort's
            # in-flight drain: the drain thread only touches the channel,
            # the steps only touch engine state.
            outcomes = self._step_cohort(buf)
            survivors: List[List] = []
            for slot, (kind, value) in zip(buf, outcomes):
                handle = slot[0]
                if kind == "done":
                    handle._result, handle._done = value, True
                elif kind == "err":
                    handle._error, handle._done = value, True
                else:
                    requests.append((slot, value))
                    survivors.append(slot)
            # Commit the new cohort state *before* touching the channel:
            # submit (whose max_batch auto-drain can run fn) may blow up
            # on a broken oracle, and when it does, finished plans must
            # already be gone and every surviving slot must still get a
            # definitive inbox — never a stale one that would silently
            # resume its plan with the previous turn's payload.
            self._bufs[cur] = buf = survivors
        # Resolve the other cohort's drain before submitting: submits
        # would only block on the channel lock the drain holds anyway,
        # and waiting here keeps drain_wait_s an honest overlap metric.
        self._await_outstanding()
        if requests:
            pending: List[Tuple[List, object]] = []
            try:
                for slot, req in requests:
                    pending.append((slot, self.client.submit(
                        req.indices, ledger=req.ledger)))
            except BaseException as err:  # noqa: BLE001 — into inboxes
                # A submit-time auto-drain failed: its poison already
                # marks every popped ticket; plans see the error at their
                # next turn (loudly — the handles raise it), exactly like
                # an async drain failure.
                submitted = {id(slot) for slot, _ in pending}
                for slot, _ in requests:
                    if id(slot) not in submitted:
                        slot[2] = err     # failed before this submit ran
                for slot, ticket in pending:
                    try:
                        slot[2] = ticket.result()
                    except BaseException as terr:  # noqa: BLE001
                        slot[2] = terr
            else:
                self.stats.drains += 1
                self._outstanding = (self._start_drain(), pending)
        self._turn = 1 - cur

    def _start_drain(self) -> DrainHandle:
        """Launch the pending tickets' coalesced drain, overlapped when
        the client supports it. Third-party `OracleClient`s without
        `drain_async` drain synchronously on the driver thread —
        identical results, no overlap."""
        start = getattr(self.client, "drain_async", None)
        if start is not None:
            return start()
        handle = DrainHandle()
        t0 = time.perf_counter()
        err: Optional[BaseException] = None
        try:
            self.client.drain()
        except BaseException as e:  # noqa: BLE001 — carried by handle
            err = e
        handle._finish(err, time.perf_counter() - t0)
        return handle

    # -- lifecycle --------------------------------------------------------

    def close(self, abandon: bool = False) -> None:
        """Finish the session: pump every submitted query to completion
        (unless `abandon`), then reject stragglers, close their plans,
        and reap the channel's drain thread (for a session-owned client
        only — a caller-shared `OracleClient` outlives the session)."""
        if self._closed:
            return
        if not abandon:
            self._pump()
        self._await_outstanding()    # settle any in-flight drain
        self._closed = True
        leftovers = self._queued + [
            (s[0], s[1]) for s in self._bufs[0] + self._bufs[1]]
        self._queued, self._bufs = [], [[], []]
        for handle, plan in leftovers:
            plan.close()
            if not handle._done:
                handle._error = RuntimeError("QuerySession abandoned")
                handle._done = True
        if self._owns_client:
            close_client = getattr(self.client, "close", None)
            if close_client is not None:
                close_client()

    def __enter__(self) -> "QuerySession":
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        self.close(abandon=exc_type is not None)
        return False
