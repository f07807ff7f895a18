"""In-memory master-data cache (paper §3.1.2, In-memory Table Updater).

The paper gives each Spark worker an embedded H2 instance holding only the
master rows for its assigned business keys. On TPU the worker-local store is
a device-resident open-addressing hash table:

  keys   : i32 [n_slots]   (-1 = empty)   — the JOIN key of the table
  values : f32|i32 [n_slots, W]           — master row payload (the
                                            table's lanes and width)
  txn    : i64 [n_slots]                  — row transaction time (watermark)

``VersionedTable`` keeps every version of a type-2 dimension (one slot per
(key, EffectiveDate)) and answers point-in-time probes (``lookup_asof``).

Slot assignment happens host-side at update time (updates are rare next to
lookups); the hot path — the probe inside the Data Transformer — goes
through the pluggable compute-backend layer (``repro.core.backend``):
``numpy`` host probing, ``jax`` jitted linear probing (``lookup_ref``
below), or the Pallas ``hash_join`` kernel on TPU. All three are
contract-identical.

Fault tolerance / elasticity (paper §3.2): ``reset_from_snapshot`` re-dumps
the compacted master topic filtered by the newly assigned business keys —
the 'cache reset trigger'. The measured cost of this dump is the Fig. 4
initialization overhead.
"""
from __future__ import annotations

import functools
from typing import Dict, Optional, Set, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.records import PAYLOAD_WIDTH

MAX_PROBES = 16


def hash32_np(keys: np.ndarray) -> np.ndarray:
    """32-bit mix (lowbias32), identical on host and device — JAX runs with
    x64 disabled, so the cache hash must be 32-bit exact on both sides."""
    with np.errstate(over="ignore"):
        x = (np.asarray(keys).astype(np.int64) & 0xFFFFFFFF).astype(np.uint32)
        x ^= x >> np.uint32(16)
        x *= np.uint32(0x7FEB352D)
        x ^= x >> np.uint32(15)
        x *= np.uint32(0x846CA68B)
        x ^= x >> np.uint32(16)
    return x


def hash32_jnp(keys: jax.Array) -> jax.Array:
    x = keys.astype(jnp.uint32)
    x = x ^ (x >> jnp.uint32(16))
    x = x * jnp.uint32(0x7FEB352D)
    x = x ^ (x >> jnp.uint32(15))
    x = x * jnp.uint32(0x846CA68B)
    x = x ^ (x >> jnp.uint32(16))
    return x


class InMemoryTable:
    asof_lane = None             # VersionedTable: the EffectiveDate lane

    def __init__(self, n_slots: int, width: int = PAYLOAD_WIDTH,
                 backend=None, dtype=np.float32):
        self.n_slots = n_slots
        self.width = width
        self._backend = backend          # name/instance; resolved lazily
        self.keys = np.full(n_slots, -1, np.int32)
        self.values = np.zeros((n_slots, width), dtype)
        self.txn = np.zeros(n_slots, np.int64)
        self.watermark = 0           # latest master txn_time seen
        self.n_rows = 0
        self.init_dump_s = 0.0       # Fig. 4: cache initialization overhead
        self._device = None          # lazily mirrored jnp arrays
        self._dirty = {"keys", "values", "txn"}   # components to re-upload
        self.version = 0             # bumped on every mutation
        self._snap = None            # memoized CacheSnapshot
        self._snap_version = -1
        self.upload_bytes = 0        # host->device bytes of the last
                                     # snapshot_view (0 when memoized)

    # ------------------------------------------------------------ updates
    def _slot_of(self, key: int) -> int:
        """Find the key's slot within the device probe budget; grow+rehash
        when a chain would exceed MAX_PROBES (the jitted lookup stops there,
        so a longer host-side chain would make the row invisible)."""
        key32 = int(np.int32(np.int64(key) & 0xFFFFFFFF))
        while True:
            h = int(hash32_np(np.array([key32]))[0] % self.n_slots)
            for p in range(MAX_PROBES):
                s = (h + p) % self.n_slots
                k = self.keys[s]
                if k == -1 or k == key32:
                    return s
            self._grow()

    def _grow(self) -> None:
        old_keys, old_vals, old_txn = self.keys, self.values, self.txn
        self.n_slots *= 2
        self.keys = np.full(self.n_slots, -1, np.int32)
        self.values = np.zeros((self.n_slots, self.width), old_vals.dtype)
        self.txn = np.zeros(self.n_slots, np.int64)
        self.n_rows = 0
        live = np.nonzero(old_keys != -1)[0]
        for s in live:
            d = self._slot_of(int(old_keys[s]))
            if self.keys[d] == -1:
                self.n_rows += 1
            self.keys[d] = old_keys[s]
            self.values[d] = old_vals[s]
            self.txn[d] = old_txn[s]
        self._device = None
        self._dirty = {"keys", "values", "txn"}

    def upsert(self, keys: np.ndarray, payloads: np.ndarray,
               txn_times: np.ndarray) -> None:
        """Last-writer-wins BY TRANSACTION TIME (not arrival order): cache
        state is then independent of snapshot/stream interleaving — the
        property the §4.1.3 consistency check relies on.

        Fully vectorized (one hash pass + one probe loop over MAX_PROBES
        steps for the whole batch): the per-row Python loop this replaces
        cost ~19us/row and sat on the GIL inside every worker's ingest
        stage — the master pump was the single largest host cost of a
        streaming step."""
        n = len(keys)
        if n == 0:
            return
        keys = np.asarray(keys, np.int64)
        txn_times = np.asarray(txn_times, np.int64)
        payloads = np.asarray(payloads, self.values.dtype)
        # watermark advances over ALL arriving rows, stale or not (same as
        # the per-row loop: it tracked skipped rows' txn times too)
        self.watermark = max(self.watermark, int(txn_times.max()))

        # one winner per key: latest txn_time, arrival order breaking ties
        # (identical to applying the rows one by one)
        order = np.lexsort((np.arange(n), txn_times, keys))
        last = np.nonzero(np.append(keys[order][1:] != keys[order][:-1],
                                    True))[0]
        win = order[last]
        key32 = (keys[win] & 0xFFFFFFFF).astype(np.int32)
        vals, txns = payloads[win], txn_times[win]

        wrote_vals = False           # any slot payload/txn written
        wrote_keys = False           # any NEW key claimed a slot
        while True:
            h = (hash32_np(key32) % np.uint32(self.n_slots)).astype(np.int64)
            pending = np.arange(len(key32))
            for p in range(MAX_PROBES):
                if not len(pending):
                    break
                cand = (h[pending] + p) % self.n_slots
                slot_keys = self.keys[cand]
                # existing slot for this key: overwrite unless stale
                hit = slot_keys == key32[pending]
                upd = pending[hit][txns[pending[hit]] >=
                                   self.txn[cand[hit]]]
                if len(upd):
                    s = (h[upd] + p) % self.n_slots
                    self.keys[s] = key32[upd]
                    self.values[s] = vals[upd]
                    self.txn[s] = txns[upd]
                    wrote_vals = True    # key lane rewritten with the SAME
                                         # content — values/txn dirty only
                # empty slot: first distinct key per slot claims it, the
                # rest continue probing (a valid sequential insert order)
                empty = np.nonzero(slot_keys == -1)[0]
                claimed = np.zeros(len(pending), bool)
                if len(empty):
                    uniq_slots, first = np.unique(cand[empty],
                                                  return_index=True)
                    winners = pending[empty[first]]
                    s = (h[winners] + p) % self.n_slots
                    self.keys[s] = key32[winners]
                    self.values[s] = vals[winners]
                    self.txn[s] = txns[winners]
                    self.n_rows += len(winners)
                    claimed[empty[first]] = True
                    wrote_keys = wrote_vals = True
                pending = pending[~(hit | claimed)]
            if not len(pending):
                break
            # probe chains exhausted: grow + rehash, retry the remainder
            keep = pending
            key32, vals, txns = key32[keep], vals[keep], txns[keep]
            self._grow()
        # device-mirror reuse: re-upload ONLY the components this upsert
        # touched. Steady-state master updates overwrite existing rows'
        # payloads, so the (large, rarely changing) key lane keeps its
        # device buffer; an all-stale batch re-uploads nothing at all.
        if wrote_keys:
            self._dirty.add("keys")
        if wrote_vals:
            self._dirty.update(("values", "txn"))
        self.version += 1

    def retain_only(self, keep_bkeys: np.ndarray,
                    bk_col: int = 1) -> Tuple[int, int]:
        """Surgical cache migration, drop side: keep ONLY the rows whose
        business key (``values[:, bk_col]`` — every master payload carries
        its equipment/business key there) is in ``keep_bkeys``; rows of
        moved-away key ranges are dropped. Returns (kept, dropped) row
        counts.

        Open addressing cannot delete in place (an emptied slot would cut
        the probe chains of keys hashed past it, making them invisible to
        the bounded device probe), so the retained rows are re-inserted
        through the vectorized ``upsert`` — still a pure LOCAL operation:
        unlike the paper's cache-reset trigger it never touches the broker
        snapshot, which is exactly what makes a rebalance keep its
        survivors warm. The watermark is preserved (it tracks the master
        STREAM, not this worker's slice of it)."""
        live = np.nonzero(self.keys != -1)[0]
        if not len(live):
            return 0, 0
        bks = self.values[live, bk_col].astype(np.int64)
        keep_sorted = np.unique(np.asarray(keep_bkeys, np.int64))
        from repro.core.partitioning import isin_sorted
        mask = isin_sorted(keep_sorted, bks)
        kept = live[mask]
        dropped = len(live) - len(kept)
        if dropped == 0:
            return len(kept), 0
        keys = self.keys[kept].astype(np.int64)   # fancy index: copies
        vals = self.values[kept]
        txns = self.txn[kept]
        watermark = self.watermark
        self.keys[:] = -1
        self.values[:] = 0
        self.txn[:] = 0
        self.n_rows = 0
        self._dirty = {"keys", "values", "txn"}
        self.version += 1
        if len(kept):
            self.upsert(keys, vals, txns)
        self.watermark = watermark
        return len(kept), dropped

    def reset_from_snapshot(self, row_keys: np.ndarray, payloads: np.ndarray,
                            txn_times: np.ndarray) -> float:
        """Paper's cache-reset trigger: wipe + re-dump compacted snapshot.
        Returns the dump wall time (Fig. 4)."""
        import time
        t0 = time.perf_counter()
        self.keys[:] = -1
        self.values[:] = 0
        self.txn[:] = 0
        self.n_rows = 0
        self.watermark = 0
        self._dirty = {"keys", "values", "txn"}
        self.version += 1
        self.upsert(row_keys, payloads, txn_times)
        self.init_dump_s = time.perf_counter() - t0
        return self.init_dump_s

    # ------------------------------------------------------------ metrics
    def stats(self) -> Dict[str, float]:
        """Health-snapshot view of the table: occupancy, mutation version,
        watermark and the last re-dump cost. Lock-free — every field is
        one GIL-atomic read."""
        return {"rows": self.n_rows, "slots": self.n_slots,
                "fill": round(self.n_rows / self.n_slots, 4)
                if self.n_slots else 0.0,
                "version": self.version, "watermark": self.watermark,
                "init_dump_s": round(self.init_dump_s, 6)}

    # ------------------------------------------------------------ lookups
    def device_state(self) -> Tuple[jax.Array, jax.Array, jax.Array]:
        """Device mirror of (keys, values, txn), component-dirty tracked:
        only arrays whose host content changed since the last mirror are
        re-uploaded (``jnp.asarray`` COPIES host->device, so mirrors
        already pinned by older ``CacheSnapshot``s stay immutable). A
        steady-state bucket whose master data hasn't moved re-uploads
        nothing — the device arrays are reused dispatch after dispatch."""
        if self._device is None or self._dirty:
            k, v, t = self._device or (None, None, None)
            if k is None or "keys" in self._dirty:
                k = jnp.asarray(self.keys)
            if v is None or "values" in self._dirty:
                v = jnp.asarray(self.values)
            if t is None or "txn" in self._dirty:
                t = jnp.asarray(self.txn)
            self._device = (k, v, t)
            self._dirty.clear()
        return self._device

    def snapshot_view(self, device: bool) -> "CacheSnapshot":
        """Immutable point-in-time view for LOCK-FREE probing. The caller
        holds the cache lock only for this call; the returned snapshot is
        safe to probe while concurrent upserts mutate the live table. For
        device backends it pins the (immutable) device mirror; for host
        backends it copies the arrays. Memoized per `version`, so in steady
        state (master data changes rarely — the paper's premise) it is a
        few attribute reads. ``upload_bytes`` says what the call
        re-uploaded to the device."""
        self.upload_bytes = 0
        if self._snap is None or self._snap_version != (self.version,
                                                        device):
            if device:
                before = self._device or (None, None, None)
                state = self.device_state()
                self.upload_bytes = sum(
                    a.nbytes for a, b in zip(state, before) if a is not b)
                self._snap = CacheSnapshot(None, None, None, self.watermark,
                                           state, backend=self._backend,
                                           asof_lane=self.asof_lane)
            else:
                self._snap = CacheSnapshot(
                    self.keys.copy(), self.values.copy(), self.txn.copy(),
                    self.watermark, None, backend=self._backend,
                    asof_lane=self.asof_lane)
            self._snap_version = (self.version, device)
        return self._snap


class CacheSnapshot:
    """Frozen view of an ``InMemoryTable`` (see ``snapshot_view``): exactly
    the read surface the compute backends touch, nothing else."""

    __slots__ = ("keys", "values", "txn", "watermark", "_device", "_backend",
                 "asof_lane")

    def __init__(self, keys, values, txn, watermark, device, backend=None,
                 asof_lane=None):
        self.asof_lane = asof_lane
        self.keys = keys
        self.values = values
        self.txn = txn
        self.watermark = watermark
        self._device = device
        self._backend = backend      # name/instance; resolved lazily

    def device_state(self):
        return self._device

    @property
    def backend(self):
        """Resolved ComputeBackend (explicit > config/env default)."""
        from repro.core.backend import ComputeBackend, get_backend
        if not isinstance(self._backend, ComputeBackend):
            self._backend = get_backend(self._backend)
        return self._backend

    def lookup(self, query_keys
               ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """Vectorized probe through the compute backend. Returns host
        (values [n, W], found [n] bool, txn_times [n])."""
        be = self.backend
        state = (self.device_state() if be.device
                 else (self.keys, self.values, self.txn))
        return be.hash_probe(query_keys, *state)


@jax.jit
def lookup_ref(query_keys: jax.Array, keys_tbl: jax.Array,
               vals_tbl: jax.Array, txn_tbl: jax.Array
               ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Pure-jnp linear probing (oracle twin of kernels/hash_join).

    The probe scan touches ONLY the key lane (4 B/slot/step); the winning
    slot index is carried through the scan and the 32 B value rows + txn
    are gathered ONCE at the end. Probing is memory-bound, so the narrow
    scan is both faster and far kinder to concurrent worker threads
    sharing a memory bus than gathering full rows every step."""
    n_slots = keys_tbl.shape[0]
    q = query_keys.astype(jnp.int32)
    h = (hash32_jnp(q) % jnp.uint32(n_slots)).astype(jnp.int32)

    def probe(carry, p):
        done, idx = carry
        cand = (h + p) % n_slots
        k = keys_tbl[cand]
        hit = (k == q) & (~done)
        empty = (k == -1) & (~done)
        idx = jnp.where(hit, cand, idx)
        done = done | hit | empty    # stop probing on hit or empty slot
        return (done, idx), None

    n = q.shape[0]
    init = (jnp.zeros(n, bool), jnp.full(n, -1, jnp.int32))
    (done, idx), _ = jax.lax.scan(probe, init, jnp.arange(MAX_PROBES))
    found = idx >= 0
    safe = jnp.maximum(idx, 0)
    val = jnp.where(found[:, None], vals_tbl[safe], 0)
    txn = jnp.where(found, txn_tbl[safe], 0)
    return val, found, txn


SCATTER_MAX = 1 << 16     # slots one in-place device update writes


@functools.partial(jax.jit, donate_argnums=(0,))
def _scatter_slots(state, idx, keys, vals, txn):
    """Write rows into a device mirror's slots ``idx``, in place."""
    k, v, t = state
    return k.at[idx].set(keys), v.at[idx].set(vals), t.at[idx].set(txn)


class VersionedTable(InMemoryTable):
    """Cache of a type-2 slowly changing dimension: one slot per version,
    keyed by the dimension's business key and told apart by the version's
    EffectiveDate (payload lane ``asof_lane``). A key's versions all lie
    inside its ``MAX_PROBES`` probe window, so a point-in-time probe
    (``lookup_asof``) sees every version a key has.

    A version that cannot be placed because its key's window is already
    filled with that key's own versions is an *overflow*: counted in
    ``overflows``, and the key's oldest version is dropped (or the new one,
    when it is the oldest). A probe whose time falls in a dropped version's
    interval then finds nothing and waits in the late buffer; it never
    finds a wrong version, since every version it can see is either the
    right one or newer than its time. A window crowded by other keys is no
    overflow: the table grows, as ``InMemoryTable`` does."""

    def __init__(self, n_slots: int, width: int, asof_lane: int,
                 backend=None, dtype=np.int32):
        super().__init__(n_slots, width, backend, dtype)
        self.asof_lane = asof_lane
        self.overflows = 0
        self._changed: list = []     # slots written since the device sync
        self._sent = 0               # bytes the last device sync sent

    def upsert(self, keys: np.ndarray, payloads: np.ndarray,
               txn_times: np.ndarray) -> None:
        """Insert versions; the same (key, EffectiveDate) again overwrites
        its slot, last writer by transaction time winning (arrival order
        breaking ties), as ``InMemoryTable.upsert`` does per key."""
        n = len(keys)
        if n == 0:
            return
        keys = np.asarray(keys, np.int64)
        txn_times = np.asarray(txn_times, np.int64)
        payloads = np.asarray(payloads, self.values.dtype)
        self.watermark = max(self.watermark, int(txn_times.max()))
        eff = payloads[:, self.asof_lane].astype(np.int64)
        order = np.lexsort((np.arange(n), txn_times, eff, keys))
        ko, eo = keys[order], eff[order]
        win = order[np.nonzero(np.append(
            (ko[1:] != ko[:-1]) | (eo[1:] != eo[:-1]), True))[0]]
        self._insert((keys[win] & 0xFFFFFFFF).astype(np.int32), eff[win],
                     payloads[win], txn_times[win])
        self.version += 1

    def device_state(self) -> Tuple[jax.Array, jax.Array, jax.Array]:
        """Device mirror of (keys, values, txn). A dimension's versions
        keep arriving, so after the first upload only the slots written
        since the last call go over, scattered into the mirror IN PLACE
        (its buffers are donated): the old mirror arrays are invalid once
        this returns, so a caller that uses a snapshot's arrays must do so
        under the same lock as this call (the concurrent runtime launches
        a worker's transform under its cache lock when it has a versioned
        cache). A rewrite of the whole table (growth, a reset, a
        migration) uploads it whole."""
        if self._device is None or self._dirty:
            self._device = tuple(jnp.asarray(np.array(a)) for a in
                                 (self.keys, self.values, self.txn))
            self._dirty.clear()
            self._changed = []
            self._sent = sum(a.nbytes for a in self._device)
        elif self._changed:
            idx = np.unique(np.concatenate(self._changed))
            self._changed = []
            for lo in range(0, len(idx), SCATTER_MAX):
                part = idx[lo:lo + SCATTER_MAX]
                pad = np.resize(part, 1 << (len(part) - 1).bit_length())
                self._device = _scatter_slots(
                    self._device, pad, self.keys[pad], self.values[pad],
                    self.txn[pad].astype(np.int32))
                self._sent += len(pad) * (8 + self.values.itemsize
                                          * (self.width + 1))
        return self._device

    def snapshot_view(self, device: bool) -> "CacheSnapshot":
        self._sent = 0
        snap = super().snapshot_view(device)
        if device:
            self.upload_bytes = self._sent
        return snap

    def compile_updates(self) -> None:
        """Compile the in-place slot update at every size it can take
        (powers of two up to ``SCATTER_MAX``), rewriting slots with their
        own values."""
        self.device_state()
        size = 1
        while size <= SCATTER_MAX:
            idx = np.arange(size) % self.n_slots
            self._device = _scatter_slots(
                self._device, idx, self.keys[idx], self.values[idx],
                self.txn[idx].astype(np.int32))
            size *= 2

    def _insert(self, key32, eff, vals, txns) -> None:
        lane = self.asof_lane
        h = (hash32_np(key32) % np.uint32(self.n_slots)).astype(np.int64)
        pending = np.arange(len(key32))
        for p in range(MAX_PROBES):
            if not len(pending):
                break
            cand = (h[pending] + p) % self.n_slots
            slot_keys = self.keys[cand]
            same = ((slot_keys == key32[pending])
                    & (self.values[cand, lane] == eff[pending]))
            upd = same & (txns[pending] >= self.txn[cand])
            s, i = cand[upd], pending[upd]
            self.values[s], self.txn[s] = vals[i], txns[i]
            self._changed.append(s)
            empty = np.nonzero(slot_keys == -1)[0]
            claimed = np.zeros(len(pending), bool)
            if len(empty):
                _, first = np.unique(cand[empty], return_index=True)
                s, i = cand[empty[first]], pending[empty[first]]
                self.keys[s], self.values[s], self.txn[s] = (
                    key32[i], vals[i], txns[i])
                self._changed.append(s)
                self.n_rows += len(i)
                claimed[empty[first]] = True
            pending = pending[~(same | claimed)]
        crowded = []
        for i in pending:            # rare: the key's window is full
            win = (h[i] + np.arange(MAX_PROBES)) % self.n_slots
            mine = win[self.keys[win] == key32[i]]
            if len(mine) < MAX_PROBES:
                crowded.append(i)
                continue
            self.overflows += 1
            oldest = mine[np.argmin(self.values[mine, lane])]
            if self.values[oldest, lane] < eff[i]:
                self.values[oldest], self.txn[oldest] = vals[i], txns[i]
                self._changed.append(np.array([oldest]))
        if crowded:
            idx = np.asarray(crowded)
            self._grow()
            self._insert(key32[idx], eff[idx], vals[idx], txns[idx])

    def _grow(self) -> None:
        live = np.nonzero(self.keys != -1)[0]
        keys, vals, txns = self.keys[live], self.values[live], self.txn[live]
        self.n_slots *= 2
        self.keys = np.full(self.n_slots, -1, np.int32)
        self.values = np.zeros((self.n_slots, self.width), vals.dtype)
        self.txn = np.zeros(self.n_slots, np.int64)
        self.n_rows = 0
        self._insert(keys, vals[:, self.asof_lane].astype(np.int64), vals,
                     txns)
        self._device = None
        self._dirty = {"keys", "values", "txn"}


def lookup_asof(query_keys: jax.Array, query_times: jax.Array,
                keys_tbl: jax.Array, vals_tbl: jax.Array, asof_lane: int
                ) -> Tuple[jax.Array, jax.Array]:
    """Point-in-time probe of a ``VersionedTable`` (traced inside the
    transform kernels): for each key, the version with the latest
    EffectiveDate (lane ``asof_lane``) not after its query time. The scan
    reads the key lane and that one value lane of each window slot and
    stops at an empty slot; the winning row is gathered once. Returns
    (values [n, W], found [n] bool)."""
    n_slots = keys_tbl.shape[0]
    q = query_keys.astype(jnp.int32)
    t = query_times.astype(jnp.int32)
    h = (hash32_jnp(q) % jnp.uint32(n_slots)).astype(jnp.int32)

    def probe(carry, p):
        done, idx, best = carry
        cand = (h + p) % n_slots
        k = keys_tbl[cand]
        # gathered element by element: slicing the lane out of the whole
        # table first would read every slot's row on each call
        eff = vals_tbl[cand, asof_lane]
        better = (k == q) & (eff <= t) & ((idx < 0) | (eff > best)) & ~done
        idx = jnp.where(better, cand, idx)
        best = jnp.where(better, eff, best)
        return (done | (k == -1), idx, best), None

    n = q.shape[0]
    init = (jnp.zeros(n, bool), jnp.full(n, -1, jnp.int32),
            jnp.zeros(n, vals_tbl.dtype))
    (_, idx, _), _ = jax.lax.scan(probe, init, jnp.arange(MAX_PROBES))
    found = idx >= 0
    val = jnp.where(found[:, None], vals_tbl[jnp.maximum(idx, 0)], 0)
    return val, found
