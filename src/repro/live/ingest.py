"""Incremental ingestion — grow a live corpus without cold rebuilds.

`IngestPlane` is the public face of `SelectionEngine._append_shards`: it
accepts appended score shards (arrays or `ScoreStore`s), delta-updates the
engine's cached state — per-shard sketches for *only* the new data merge
additively into the global sketch, normalizers refresh from the merged
sketch, and every cached per-(scheme, kappa) chunk-mass CDF rebuilds from
cached chunk masses in O(n_chunks) without re-reading any old record —
and installs the result as a new corpus *epoch*.

Epoch semantics carry the correctness story:

  * installs are atomic (one attribute assignment); an in-flight plan that
    pinned its epoch keeps computing against a frozen, consistent corpus,
  * results over any epoch are bit-for-bit what a cold engine build over
    exactly that corpus would produce (`tests/test_live.py` asserts this
    for RT/PT/JT at workers 1/4/8),
  * `shards_since(epoch)` names the shards an epoch transition added —
    the unit the standing-query plane re-emits over.
"""
from __future__ import annotations

import threading
from typing import Dict, List, Optional, Sequence, Union

import numpy as np
from jax.profiler import TraceAnnotation

from repro.core.engine import CorpusState, SelectionEngine


class IngestPlane:
    """Appends score shards to a `SelectionEngine`, one epoch per append.

    >>> import numpy as np
    >>> from repro.core.engine import SelectionEngine
    >>> eng = SelectionEngine([np.linspace(0, 1, 512, dtype=np.float32)],
    ...                       num_bins=32, use_kernel=False)
    >>> plane = IngestPlane(eng)
    >>> epoch = plane.append(np.linspace(0, 1, 256, dtype=np.float32))
    >>> (epoch, eng.epoch, eng.n_total, plane.shards_since(0))
    (1, 1, 768, [1])
    >>> eng.close()
    """

    def __init__(self, engine: SelectionEngine):
        self.engine = engine
        self._lock = threading.Lock()
        # epoch -> shard count at that epoch, for shards_since(); seeded
        # with the engine's current epoch so a plane attached late still
        # resolves deltas from its attach point.
        self._shard_count_at: Dict[int, int] = {
            engine.epoch: len(engine.shards)}
        self.appends = 0             # epochs installed through this plane
        self.records_ingested = 0    # records those epochs added

    @property
    def epoch(self) -> int:
        """The engine's current corpus epoch."""
        return self.engine.epoch

    def append(self, shards: Union[Sequence, np.ndarray, object],
               use_kernel: Optional[bool] = None) -> int:
        """Append one shard (array / ScoreStore) or a sequence of shards;
        returns the new epoch number.

        Only the appended data is sketched (`use_kernel` overrides the
        engine's construction-time kernel choice for that pass); all other
        state updates are O(n_chunks) rebuilds from cached masses. Safe to
        call concurrently with query execution — in-flight plans keep
        their pinned epoch. Runs inside the `supg.append` profiler span.
        """
        if isinstance(shards, (list, tuple)):
            batch = list(shards)
        else:
            batch = [shards]
        with self._lock, TraceAnnotation("supg.append",
                                         shards=len(batch)) as trace:
            before = self.engine.n_total
            state = self.engine._append_shards(batch, use_kernel=use_kernel)
            self._shard_count_at[state.epoch] = len(state.shards)
            self.appends += 1
            self.records_ingested += state.n_total - before
            trace.set_metadata(records=state.n_total - before)
            return state.epoch

    def shards_since(self, epoch: int) -> List[int]:
        """Shard ids appended strictly after `epoch` (through this plane).

        The re-emission unit: a standing query certified at `epoch` only
        needs a threshold walk over these shards to catch up to the
        current corpus.
        """
        with self._lock:
            if epoch not in self._shard_count_at:
                raise ValueError(
                    f"epoch {epoch} was not recorded by this IngestPlane "
                    f"(known: {sorted(self._shard_count_at)})")
            return list(range(self._shard_count_at[epoch],
                              len(self.engine.shards)))

    def pin(self) -> CorpusState:
        """Snapshot the current epoch (delegates to `engine.pin()`).
        Counts as a live reference — pair with `unpin` so epoch GC can
        free superseded epochs."""
        return self.engine.pin()

    def unpin(self, state: CorpusState) -> None:
        """Release a `pin` reference (delegates to `engine.unpin()`)."""
        self.engine.unpin(state)
