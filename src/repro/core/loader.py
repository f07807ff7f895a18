"""Target Database Updater (paper §3.1.2): partition-parallel load of
transformed facts into the star-schema warehouse.

``StarSchemaWarehouse`` holds one fact table (OEE fact grains) plus the
equipment dimension; loads are per-partition appends (each partition
'executes its query statements independently'). Two read paths:

* the ad-hoc OLAP path (``query_oee`` / ``kpi_rollup`` / ``fact_table``)
  — full-rescan aggregates. All three read a pinned per-partition view of
  COMMITTED state (``read_view``): a load appends its chunks and bumps the
  commit sequence under one lock acquisition, and a view pins the chunk
  log at a commit boundary — so a report that issues several queries
  against one view can never observe a partition mid-``load`` from a
  concurrent worker (the torn-report race the serving layer also closes);

* the serving path — every load publishes its fact block (plus the
  records' CDC event-time stamps) as a delta to an attached
  ``repro.serving.MaterializedViewEngine``, which maintains report views
  incrementally in O(delta). ``attach_serving`` replays the committed
  chunk log first, so views cover history loaded before attachment.
"""
from __future__ import annotations

import contextlib
import dataclasses
import threading
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro.core.partitioning import partition_bounds, partition_of
from repro.core.transformer import FACT_COLUMNS
from repro.observability.tracer import NULL_TRACER


@dataclasses.dataclass(frozen=True)
class WarehouseView:
    """A pinned, immutable view of committed warehouse state: the chunk
    log as of one commit boundary. Everything a reader computes from one
    view is mutually consistent no matter how many concurrent loads land
    while it is held."""

    chunks: Tuple[np.ndarray, ...]           # committed fact blocks, in
    seq: int                                 # commit order
    rows: int


class KeyedTable:
    """A dimension the warehouse updates in place: int rows keyed by one
    column, with a host hash index (open addressing over the key, the
    cache's 32-bit hash, grown at half load) from key to row. ``applied``
    counts the CDC records applied to each row, which is what an
    exactly-once check reads."""

    def __init__(self, key_col: int, keep: Tuple[int, ...] = (),
                 capacity: int = 1024):
        self.key_col = key_col
        self.keep = np.asarray(keep, np.int64)
        self.rows = np.zeros((capacity, 0), np.int32)   # width: first rows
        self.applied = np.zeros(capacity, np.int32)
        self.n = 0
        self._keys = np.full(2 * capacity, -1, np.int64)
        self._pos = np.zeros(2 * capacity, np.int64)

    def _probe(self, keys: np.ndarray):
        """(row position or -1, index slot or -1) per key; a key's slot is
        where it lives or the first empty slot of its chain."""
        from repro.core.cache import hash32_np
        mask = len(self._keys) - 1
        h = hash32_np(keys).astype(np.int64)
        pos = np.full(len(keys), -1, np.int64)
        slot = np.full(len(keys), -1, np.int64)
        pending = np.arange(len(keys))
        p = 0
        while len(pending):
            cand = (h[pending] + p) & mask
            k = self._keys[cand]
            hit, empty = k == keys[pending], k == -1
            pos[pending[hit]] = self._pos[cand[hit]]
            slot[pending[hit | empty]] = cand[hit | empty]
            pending = pending[~(hit | empty)]
            p += 1
        return pos, slot

    def _index(self, keys: np.ndarray, at: np.ndarray) -> None:
        """Index new distinct ``keys`` at row positions ``at``."""
        pending = np.arange(len(keys))
        while len(pending):
            _, slot = self._probe(keys[pending])
            _, first = np.unique(slot, return_index=True)
            won = pending[first]
            self._keys[slot[first]] = keys[won]
            self._pos[slot[first]] = at[won]
            pending = np.setdiff1d(pending, won, assume_unique=True)

    def _reserve(self, extra: int) -> None:
        need = self.n + extra
        if need > len(self.rows):
            cap = max(need, 2 * len(self.rows))
            self.rows = np.concatenate(
                [self.rows, np.zeros((cap - len(self.rows),
                                      self.rows.shape[1]), np.int32)])
            self.applied = np.concatenate(
                [self.applied, np.zeros(cap - len(self.applied), np.int32)])
        if 2 * need > len(self._keys):
            size = len(self._keys)
            while 2 * need > size:
                size *= 2
            self._keys = np.full(size, -1, np.int64)
            self._pos = np.zeros(size, np.int64)
            self._index(self.rows[:self.n, self.key_col].astype(np.int64),
                        np.arange(self.n))

    def upsert(self, rows: np.ndarray) -> int:
        """Apply ``rows`` in order: a new key inserts its row; a known key
        is overwritten, each ``keep`` column keeping its stored value where
        the new row carries NULL (-1). A key met twice in one call is
        applied twice, in order (rounds of first occurrences). Returns how
        many keys were new."""
        rows = np.asarray(rows, np.int32)
        if not self.n and self.rows.shape[1] != rows.shape[1]:
            self.rows = np.zeros((len(self.rows), rows.shape[1]), np.int32)
        keys = rows[:, self.key_col].astype(np.int64)
        order = np.lexsort((np.arange(len(keys)), keys))
        ks = keys[order]
        start = np.r_[0, np.nonzero(ks[1:] != ks[:-1])[0] + 1]
        rank = np.empty(len(keys), np.int64)
        rank[order] = np.arange(len(keys)) - np.repeat(
            start, np.diff(np.r_[start, len(keys)]))
        inserted = 0
        for r in range(int(rank.max()) + 1 if len(rank) else 0):
            idx = np.nonzero(rank == r)[0]
            self._reserve(len(idx))
            pos, _ = self._probe(keys[idx])
            new = pos < 0
            at = self.n + np.arange(int(new.sum()))
            self._index(keys[idx[new]], at)
            self.rows[at] = rows[idx[new]]
            self.applied[at] = 1
            self.n += len(at)
            inserted += len(at)
            old = pos[~new]
            if len(old):
                upd = rows[idx[~new]]
                if len(self.keep):
                    kept = upd[:, self.keep]
                    upd[:, self.keep] = np.where(
                        kept == -1, self.rows[old][:, self.keep], kept)
                self.rows[old] = upd
                self.applied[old] += 1
        return inserted

    def table(self) -> Tuple[np.ndarray, np.ndarray]:
        """(rows, applied counts), in insertion order (copies)."""
        return self.rows[:self.n].copy(), self.applied[:self.n].copy()

    def restore(self, rows: np.ndarray, applied: np.ndarray) -> None:
        """Replace the contents with ``table()``'s output (recovery)."""
        self.rows = np.array(rows, np.int32)
        self.applied = np.array(applied, np.int32)
        self.n = len(self.rows)
        size = len(self._keys)
        while size < 2 * self.n:
            size *= 2
        self._keys = np.full(size, -1, np.int64)
        self._pos = np.zeros(size, np.int64)
        self._index(self.rows[:, self.key_col].astype(np.int64),
                    np.arange(self.n))


class KeyedShards:
    """A keyed target split into one ``KeyedTable`` per owner (the workers
    present when the target is made), each behind its own lock, so the
    load stages' upserts do not queue on one lock. A fixed map sends each
    static partition (``partition_of`` of the business key, the stable
    hash ``load_partitioned`` uses, never the adaptive routing table) to
    the shard of the worker that owned it when the target was made. Every
    record of a key carries one business key, so a key's row lives in
    exactly one shard for the table's life. A rebalance moves no row: a
    moved partition stays in its shard, and its new owner takes that
    shard's lock for those rows (they count as ``foreign``)."""

    def __init__(self, key_col: int, keep: Tuple[int, ...],
                 owners: Sequence[str], shard_of_partition: Sequence[int]):
        self.key_col = key_col
        self.keep = tuple(keep)
        self.owners = tuple(owners)
        self.shard_of_partition = np.asarray(shard_of_partition, np.int64)
        self.shards = [KeyedTable(key_col, keep) for _ in self.owners]
        self.locks = [threading.Lock() for _ in self.owners]

    def upsert(self, block: np.ndarray,
               tracer=NULL_TRACER) -> Tuple[int, Dict[str, int]]:
        """Apply one block: target rows with each record's business key
        as a last column, so the key that places a row travels with it
        through any slice of the block. The rows are grouped by shard, and
        each group goes in ONE ``KeyedTable.upsert`` under its shard's
        lock, in block order (a key's records stay in log order). Returns
        (keys inserted, {shard owner: rows applied in its shard})."""
        block = np.asarray(block)
        if not len(block):
            return 0, {}
        rows = block[:, :-1]
        shard = self.shard_of_partition[partition_of(
            block[:, -1].astype(np.int64), len(self.shard_of_partition))]
        if (shard == shard[0]).all():      # a stage's own rows: no sort
            groups = [(int(shard[0]), rows)]
        else:
            order = np.argsort(shard, kind="stable")
            ks, first = np.unique(shard[order], return_index=True)
            groups = [(int(k), rows[idx]) for k, idx in
                      zip(ks, np.split(order, first[1:]))]
        inserted = 0
        for k, part in groups:
            with tracer.span("load.upsert_lock") as sp:
                self.locks[k].acquire()
                sp.put("records", len(part))
            try:
                inserted += self.shards[k].upsert(part)
            finally:
                self.locks[k].release()
        return inserted, {self.owners[k]: len(part) for k, part in groups}

    def _tables(self) -> List[Tuple[np.ndarray, np.ndarray]]:
        """Every shard's ``table()``, taken under all the shard locks at
        once (in shard order; an upsert holds one at a time)."""
        with contextlib.ExitStack() as stack:
            for lk in self.locks:
                stack.enter_context(lk)
            return [t.table() for t in self.shards]

    def table(self) -> Tuple[np.ndarray, np.ndarray]:
        """(rows, applied counts) of the whole target: the shards
        concatenated in shard order, each in insertion order (copies)."""
        parts = [p for p in self._tables() if len(p[0])] or [
            self.shards[0].table()]
        return (np.concatenate([r for r, _ in parts]),
                np.concatenate([a for _, a in parts]))

    def export(self) -> Dict:
        return {"owners": list(self.owners),
                "shard_of_partition": self.shard_of_partition.copy(),
                "shards": [{"rows": r, "applied": a}
                           for r, a in self._tables()]}

    @classmethod
    def restored(cls, key_col: int, keep: Tuple[int, ...],
                 state: Dict) -> "KeyedShards":
        """The shards and map of ``export()``'s output, whatever workers
        the restoring pipeline has (``table()`` reads as it did)."""
        out = cls(key_col, keep, state["owners"],
                  state["shard_of_partition"])
        for t, sh in zip(out.shards, state["shards"]):
            t.restore(sh["rows"], sh["applied"])
        return out


class StarSchemaWarehouse:
    """Loads are thread-safe: the concurrent runtime's load stages append
    from one thread per worker, so the chunk log, commit sequence and
    delta publication are guarded by a single lock (the numpy split work
    stays outside it). A keyed target takes its shards' locks instead, and
    bumps the load counters under ``_count_lock`` alone."""

    def __init__(self, backend=None, tracer=NULL_TRACER):
        self._chunk_log: List[np.ndarray] = []   # committed blocks, in order
        self._lock = threading.Lock()
        self._count_lock = threading.Lock()
        self._serving = None                 # MaterializedViewEngine (opt.)
        self._shards = None                  # ShardOwnership (opt.)
        # per-shard chunk sub-logs, chunk-aligned: _shard_logs[k][i] holds
        # chunk i's rows whose business key the ownership routes to shard
        # k — maintained incrementally at commit time (the write path
        # never moves rows across shards)
        self._shard_logs: List[List[np.ndarray]] = []
        self.backend = backend       # pipeline's ComputeBackend (or None)
        self.tracer = tracer         # a keyed target's lock waits
        self.rows_loaded = 0
        self.load_calls = 0
        self.commit_seq = 0
        # running per-unit KPI aggregate fed by the fused
        # transform_and_rollup dispatches (an O(1) read path next to the
        # kpi_rollup full rescan); rows loaded WITHOUT a rollup — legacy
        # per-partition loops, the record-at-a-time baseline — gap it
        self._kpi_running: Optional[np.ndarray] = None
        self._kpi_gap_rows = 0
        self.keyed: Optional[KeyedShards] = None   # see ``keyed_target``

    def keyed_target(self, key_col: int, keep: Tuple[int, ...],
                     owners: Sequence[str],
                     owner_of: Sequence[str]) -> KeyedShards:
        """Make the warehouse's target a keyed table that ``upsert``
        updates in place (a dimension), in place of the appended fact
        chunk log: one shard per name in ``owners``, static partition
        ``p`` (of ``len(owner_of)``) in the shard of ``owner_of[p]``."""
        with self._lock:
            self.keyed = KeyedShards(
                key_col, keep, owners,
                [list(owners).index(o) for o in owner_of])
            return self.keyed

    def upsert(self, block: np.ndarray) -> Tuple[int, Dict[str, int]]:
        """Apply one block (target rows, each with its record's business
        key as a last column) to the keyed target, in order per key, under
        the locks of the shards its keys map to (``KeyedShards.upsert``).
        Counts every row as loaded and every shard call as a load call.
        Returns (rows that updated a key already present, {shard owner:
        rows applied in its shard}). No view engine reads a keyed target:
        folding updates would need retraction. The chunk log
        (``commit_seq``) does not hold a keyed target; ``export_state``
        captures it whole."""
        if self._serving is not None:
            raise ValueError("a keyed target has no serving views")
        inserted, placed = self.keyed.upsert(block, self.tracer)
        with self._count_lock:
            self.rows_loaded += len(block)
            self.load_calls += len(placed)
        return len(block) - inserted, placed

    # ------------------------------------------------------------ serving hook
    def attach_serving(self, engine, replay_from: int = 0):
        """Wire a view engine: every committed load is published as one
        fact delta, in commit order (the publish happens under the load
        lock, so delta order == chunk-log order — what makes the engine's
        ``rebuild`` oracle byte-identical). History already loaded is
        replayed first, starting at chunk ``replay_from`` — recovery
        passes the engine's restored ``deltas_folded`` so only the
        post-checkpoint suffix replays (every committed chunk is
        non-empty, so chunk indices and delta sequence align 1:1).
        Idempotent for an already-attached engine (the recovery path
        attaches before handing the pipeline to a cluster whose
        constructor attaches again — a second full replay would
        double-fold history). Returns the engine for chaining."""
        with self._lock:
            if engine is self._serving:
                return engine
            for chunk in self._chunk_log[replay_from:]:
                engine.publish(chunk)
            self._serving = engine
        return engine

    # ------------------------------------------------------------- shard plane
    def _split_chunk(self, block: np.ndarray) -> None:
        """Lock-held: append one committed chunk's rows to the per-shard
        sub-logs (business key = fact col 0, routed through the attached
        ``ShardOwnership``). Row order within each shard's slice follows
        the chunk's order, so concatenating every shard's slices and
        canonical-sorting reproduces the chunk log byte-for-byte."""
        owner = self._shards.shard_of_keys(block[:, 0].astype(np.int64))
        for k in range(self._shards.n_shards):
            self._shard_logs[k].append(block[owner == k])

    def attach_shards(self, ownership) -> None:
        """Wire a ``repro.runtime.shard_plane.ShardOwnership``: every
        committed chunk is (and history retroactively gets) split into
        per-shard sub-logs, so each mesh shard holds only the fact rows
        of its owned business-key ranges. The primary chunk log — the
        commit/durability source of truth — is untouched; the split is a
        derived placement, which is what keeps the warehouse
        byte-identical to the unsharded one by construction."""
        with self._lock:
            self._shards = ownership
            self._shard_logs = [[] for _ in range(ownership.n_shards)]
            for chunk in self._chunk_log:
                self._split_chunk(chunk)

    def reown_shards(self, ownership) -> Dict:
        """Surgical re-split for a new routing epoch (the warehouse twin
        of ``ShardedViewEngine.reown``): chunks whose rows all keep their
        owner are left alone — only chunks containing a moved key have
        their per-shard slices rebuilt. Returns {chunks_resplit,
        rows_moved}. No-op unless shards are attached."""
        with self._lock:
            old = self._shards
            if old is None:
                return {"chunks_resplit": 0, "rows_moved": 0}
            K = ownership.n_shards
            if K != old.n_shards:
                raise ValueError(
                    f"reown_shards: shard count is fixed for the plane's "
                    f"lifetime ({old.n_shards} != {K}); detach and "
                    f"attach_shards to resize")
            resplit = 0
            moved_rows = 0
            for i, chunk in enumerate(self._chunk_log):
                keys = chunk[:, 0].astype(np.int64)
                ow_new = ownership.shard_of_keys(keys)
                moved = int((old.shard_of_keys(keys) != ow_new).sum())
                if not moved:
                    continue
                resplit += 1
                moved_rows += moved
                for k in range(K):
                    self._shard_logs[k][i] = chunk[ow_new == k]
            self._shards = ownership
            return {"chunks_resplit": resplit, "rows_moved": moved_rows}

    def shard_fact_table(self, shard: int) -> np.ndarray:
        """One shard's resident fact rows (its owned business-key ranges
        only), in commit order."""
        with self._lock:
            chunks = [c for c in self._shard_logs[shard] if len(c)]
            if not chunks:
                return np.zeros((0, len(FACT_COLUMNS)), np.float32)
            return np.concatenate(chunks)

    def shard_rows(self) -> List[int]:
        """[n_shards] resident row counts — the warehouse-side imbalance
        signal."""
        with self._lock:
            return [int(sum(len(c) for c in log))
                    for log in self._shard_logs]

    # ------------------------------------------------------------- durability
    def export_state(self, from_seq: int = 0) -> Dict:
        """Journal capture at a commit boundary: the chunk-log SUFFIX
        past ``from_seq`` (``commit_seq == len(_chunk_log)`` — one
        committed chunk per commit) plus the full counter state."""
        with self._lock, self._count_lock:
            return {
                "chunks": list(self._chunk_log[from_seq:]),
                "seq": int(self.commit_seq),
                "rows": int(self.rows_loaded),
                "load_calls": int(self.load_calls),
                "kpi_running": (None if self._kpi_running is None
                                else self._kpi_running.copy()),
                "kpi_gap_rows": int(self._kpi_gap_rows),
                # a keyed target is captured whole at every step: its
                # shards and their partition map
                "keyed": (None if self.keyed is None
                          else self.keyed.export()),
            }

    def restore_state(self, state: Dict) -> None:
        """Cold-restart restore into an empty warehouse. ``state`` is the
        journal-accumulated form (chunks = the FULL committed log). Must
        run before ``attach_serving``: the chunks land silently, and the
        serving replay decides separately how much suffix to re-publish."""
        with self._lock:
            assert not self._chunk_log and self._serving is None, \
                "restore_state requires a fresh warehouse"
            chunks = [np.asarray(c, np.float32) for c in state["chunks"]]
            if len(chunks) != int(state["seq"]):
                raise IOError(
                    f"warehouse restore: {len(chunks)} chunks for commit "
                    f"seq {state['seq']}")
            self._chunk_log = chunks
            self.commit_seq = int(state["seq"])
            self.rows_loaded = int(state["rows"])
            self.load_calls = int(state["load_calls"])
            self._kpi_running = (None if state["kpi_running"] is None
                                 else np.asarray(state["kpi_running"]))
            self._kpi_gap_rows = int(state["kpi_gap_rows"])
            if state.get("keyed") is not None:
                self.keyed = KeyedShards.restored(
                    self.keyed.key_col, self.keyed.keep, state["keyed"])

    def _commit(self, block: np.ndarray,
                event_times: Optional[np.ndarray],
                rollup: Optional[np.ndarray] = None,
                routing_epoch: Optional[int] = None) -> None:
        """Lock-held: record the block in the committed chunk log, bump the
        commit sequence, fold the fused rollup into the running KPI
        aggregate, publish the delta (stamped with the routing epoch the
        records were processed under, for migration observability)."""
        self._chunk_log.append(block)
        if self._shards is not None:
            self._split_chunk(block)
        self.commit_seq += 1
        if rollup is not None:
            if self._kpi_running is None:
                self._kpi_running = np.zeros_like(rollup)
            if self._kpi_running.shape == rollup.shape:
                self._kpi_running = self._kpi_running + rollup
            else:                     # mixed n_units producers: no O(1) path
                self._kpi_gap_rows += len(block)
        else:
            self._kpi_gap_rows += len(block)
        if self._serving is not None:
            self._serving.publish(block, event_times,
                                  routing_epoch=routing_epoch)

    # -------------------------------------------------------------- load paths
    def load(self, partition: int, facts: np.ndarray,
             event_times: Optional[np.ndarray] = None,
             rollup: Optional[np.ndarray] = None,
             routing_epoch: Optional[int] = None) -> None:
        """Per-partition append (the caller already split by partition)."""
        if len(facts) == 0:
            return
        facts = np.asarray(facts)
        with self._lock:
            self.rows_loaded += len(facts)
            self.load_calls += 1
            self._commit(facts, event_times, rollup, routing_epoch)

    def load_partitioned(self, facts: np.ndarray, n_partitions: int,
                         event_times: Optional[np.ndarray] = None,
                         rollup: Optional[np.ndarray] = None,
                         routing_epoch: Optional[int] = None) -> int:
        """Group a coalesced fact block by business-key partition (fact
        col 0 IS the business key — each partition's rows land contiguous,
        'executing its query statements independently') and commit it as
        ONE block. The numpy sort happens outside the lock; the append,
        commit-sequence bump and serving delta land under ONE acquisition
        (concurrent workers' load stages share this lock, so per-partition
        locking would contend ~n_partitions times per dispatch — and a
        reader pinning a view can never see half a load).

        The chunk layout deliberately uses the STABLE static hash, never
        the queue's adaptive routing table: the grouping of one fact set
        is then invariant to routing epochs, so serving-view folds (whose
        segment ids come from fact columns alone — partition-stable by
        construction) and the chunk log replay stay byte-identical across
        repartitions. ``routing_epoch`` is carried as a stamp for
        observability only; it never influences the layout."""
        n = len(facts)
        if n == 0:
            return 0
        order, bounds = partition_bounds(facts[:, 0].astype(np.int64),
                                         n_partitions)
        sorted_facts = facts[order]
        sorted_times = (np.asarray(event_times, np.float64)[order]
                        if event_times is not None else None)
        n_hit = sum(1 for p in range(n_partitions)
                    if bounds[p + 1] > bounds[p])
        with self._lock:
            self.rows_loaded += n
            self.load_calls += n_hit     # one logical append per partition
            self._commit(sorted_facts, sorted_times, rollup, routing_epoch)
        return n

    # -------------------------------------------------------------- read paths
    def read_view(self) -> WarehouseView:
        """Pin the committed state at the current commit boundary. The
        returned chunks are the loaded arrays themselves (append-only, by
        convention never mutated) — pinning costs one tuple copy."""
        with self._lock:
            return WarehouseView(chunks=tuple(self._chunk_log),
                                 seq=self.commit_seq, rows=self.rows_loaded)

    def kpi_running(self) -> Optional[np.ndarray]:
        """The running per-unit KPI aggregate [n_units, 5] accumulated from
        the fused ``transform_and_rollup`` dispatches at load time — an
        O(1) read that never rescans the fact table. Returns None when any
        committed rows arrived without a rollup (legacy per-partition
        loops, the baseline), because the aggregate would under-count;
        ``kpi_rollup`` below remains the full-rescan oracle it is
        parity-tested against."""
        with self._lock:
            if self._kpi_running is None or self._kpi_gap_rows:
                return None
            return self._kpi_running.copy()

    def kpi_rollup(self, n_units: int, backend=None,
                   view: Optional[WarehouseView] = None) -> np.ndarray:
        """Per-equipment KPI sums [n_units, 5] (availability, performance,
        quality, oee, count) via the compute backend's segmented reduce —
        the full-rescan reference the serving layer's incremental views
        are parity-tested against. Selection: explicit arg > the
        pipeline's configured backend > env/default."""
        from repro.core.backend import get_backend
        be = get_backend(backend or self.backend)
        return be.segment_reduce(self.fact_table(view), n_units)

    def fact_table(self, view: Optional[WarehouseView] = None) -> np.ndarray:
        if view is None:
            view = self.read_view()
        if not view.chunks:
            return np.zeros((0, len(FACT_COLUMNS)), np.float32)
        return np.concatenate(view.chunks)

    def canonical_fact_table(self, view: Optional[WarehouseView] = None
                             ) -> np.ndarray:
        """Fact table in a load-order-independent canonical order (full-row
        lexicographic sort). Two runs produced the same warehouse iff their
        canonical tables are byte-identical — the concurrency test's
        equality oracle, immune to thread interleaving of loads."""
        t = self.fact_table(view)
        if not len(t):
            return t
        return t[np.lexsort(t.T[::-1])]

    def query_oee(self, equipment_id: Optional[int] = None,
                  view: Optional[WarehouseView] = None) -> Dict[str, float]:
        """OLAP aggregate: mean KPI per (optionally one) equipment unit.
        Pass one ``read_view()`` across several calls to make a multi-query
        report consistent under concurrent loads."""
        t = self.fact_table(view)
        if equipment_id is not None:
            t = t[t[:, 0].astype(np.int64) == equipment_id]
        if len(t) == 0:
            return {k: float("nan") for k in
                    ("availability", "performance", "quality", "oee", "rows")}
        return {
            "availability": float(t[:, 3].mean()),
            "performance": float(t[:, 4].mean()),
            "quality": float(t[:, 5].mean()),
            "oee": float(t[:, 6].mean()),
            "rows": float(len(t)),
        }
