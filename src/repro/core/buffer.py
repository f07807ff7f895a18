"""Operational Message Buffer (paper §3.1.2 / §3.2, 'unsynchronized
consistency').

Operational records whose master data has not yet arrived are buffered with
their transaction time. At each new operational batch the Data Transformer
retries exactly the buffered records whose ``txn_time`` is older than the
In-memory cache watermark ('only reprocesses buffer messages with
transaction dates older than the latest transaction date from the In-memory
cache, which avoids reprocessing operational messages that still have no
master data').

The buffer state lives in the coordinator's replicated store (the paper used
Zookeeper) so any worker can resume reprocessing after a failure: on a
§4.1.3 failover, ``repro.runtime.cluster.ConcurrentCluster`` drains a dead
worker's buffer into a survivor and re-homes every buffered record to its
partition's current owner.
"""
from __future__ import annotations

from typing import Optional, Tuple

import numpy as np

from repro.core.records import RecordBatch


class OperationalMessageBuffer:
    """``ordered`` keeps the held records in log (LSN) order, so that a
    retry sweep, which takes the oldest ready records first, never takes a
    later record of a row key without the earlier ones, and a record put
    back after a retry goes in front of the later records of its key (a
    keyed target applies its records in log order per key)."""

    def __init__(self, capacity: int, ordered: bool = False):
        self.capacity = capacity
        self.ordered = ordered
        self._batch: RecordBatch = RecordBatch.empty()
        # per held record: owed a retry (held against caches that may
        # predate its master rows, or one of them arrived since; see
        # ``pop_ready``'s ``keys``)
        self._fresh = np.zeros(0, bool)
        self.dropped = 0
        self.total_buffered = 0
        self.total_retried = 0

    def __len__(self) -> int:
        return len(self._batch)

    def row_keys(self) -> np.ndarray:
        """Row keys of the held records, in hold order."""
        return self._batch.row_key

    def peek(self) -> RecordBatch:
        """The held records, in hold order (not removed)."""
        return self._batch

    def push(self, late: RecordBatch, fresh: bool = True) -> None:
        """Hold ``late``. ``fresh``: the records were transformed against
        a cache snapshot that may predate master rows already arrived (a
        retry sweep's records, tried just now, are not)."""
        if not len(late):
            return
        self.total_buffered += len(late)
        merged = RecordBatch.concat([self._batch, late])
        flags = np.concatenate([self._fresh, np.full(len(late), fresh)])
        if self.ordered:
            order = np.argsort(merged.lsn, kind="stable")
            merged, flags = merged.take(order), flags[order]
        if len(merged) > self.capacity:
            # drop oldest beyond capacity (recorded; tests assert zero drops
            # under the paper's workloads)
            self.dropped += len(merged) - self.capacity
            keep = np.arange(len(merged) - self.capacity, len(merged))
            merged, flags = merged.take(keep), flags[keep]
        self._batch, self._fresh = merged, flags

    def pop_ready(self, watermark: int, limit: Optional[int] = None,
                  keys: Optional[np.ndarray] = None) -> RecordBatch:
        """Remove and return records eligible for retry (txn_time <=
        watermark). ``limit`` bounds one retry sweep (oldest-first), so a
        mass-late cold start is drained in micro-batches instead of one
        giant dispatch. ``keys`` (sorted business keys whose master rows
        arrived since the last sweep) narrows the sweep to the records
        those rows can complete, and the fresh ones; a record it leaves
        out could only miss again."""
        if not len(self._batch):
            return RecordBatch.empty()
        ready_mask = self._batch.txn_time <= watermark
        if keys is not None:
            # a record whose key arrived stays owed a retry until one
            # takes it (the watermark may hold it back past this sweep)
            from repro.core.partitioning import isin_sorted
            self._fresh |= isin_sorted(keys, self._batch.business_key)
            ready_mask &= self._fresh
        if limit is not None and ready_mask.sum() > limit:
            keep_off = np.nonzero(ready_mask)[0][limit:]
            ready_mask = ready_mask.copy()
            ready_mask[keep_off] = False
            self._fresh[keep_off] = True      # still owed a retry
        ready = self._batch.filter(ready_mask)
        self._batch = self._batch.filter(~ready_mask)
        self._fresh = self._fresh[~ready_mask]
        self.total_retried += len(ready)
        return ready

    def drain(self) -> RecordBatch:
        """Remove and return ALL buffered records (failover handoff: a dead
        worker's replicated buffer is adopted by a survivor)."""
        out = self._batch
        self._batch = RecordBatch.empty()
        self._fresh = np.zeros(0, bool)
        return out

    # ---------------------------------------------------------- durability
    def export_state(self) -> dict:
        return {"batch": self._batch.as_dict(), "dropped": self.dropped}

    @staticmethod
    def restore(state: dict, capacity: int,
                ordered: bool = False) -> "OperationalMessageBuffer":
        buf = OperationalMessageBuffer(capacity, ordered)
        buf._batch = RecordBatch(**{k: np.asarray(v)
                                    for k, v in state["batch"].items()})
        buf._fresh = np.ones(len(buf._batch), bool)
        buf.dropped = state.get("dropped", 0)
        return buf


class DeadLetterBuffer:
    """Quarantine for poison records — operational records whose transform
    deterministically raises. Instead of crash-looping the worker, the load
    stage commits their offsets (a quarantined record counts as *handled*:
    it will never replay) and parks the records here for operator triage.

    Append-only during a run; ``drain()`` is the operator's exit (see
    docs/OPERATIONS.md). Exported/restored with worker state so a
    checkpoint+recovery cannot silently lose quarantined records whose
    offsets are already committed."""

    def __init__(self):
        self._batch: RecordBatch = RecordBatch.empty()
        self.reasons: list = []
        self.total_quarantined = 0

    def __len__(self) -> int:
        return len(self._batch)

    def push(self, dead: RecordBatch, reason: str = "transform-error") -> None:
        if not len(dead):
            return
        self.total_quarantined += len(dead)
        self.reasons.append({"reason": reason, "records": int(len(dead))})
        self._batch = RecordBatch.concat([self._batch, dead])

    def peek(self) -> RecordBatch:
        return self._batch

    def drain(self) -> RecordBatch:
        out = self._batch
        self._batch = RecordBatch.empty()
        self.reasons = []
        return out

    # ---------------------------------------------------------- durability
    def export_state(self) -> dict:
        return {"batch": self._batch.as_dict(),
                "reasons": list(self.reasons),
                "total": self.total_quarantined}

    @staticmethod
    def restore(state: Optional[dict]) -> "DeadLetterBuffer":
        dlq = DeadLetterBuffer()
        if state is None:     # journal predates the dead-letter plane
            return dlq
        dlq._batch = RecordBatch(**{k: np.asarray(v)
                                    for k, v in state["batch"].items()})
        dlq.reasons = list(state.get("reasons", []))
        dlq.total_quarantined = int(state.get("total", len(dlq._batch)))
        return dlq
