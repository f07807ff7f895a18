"""Compute-backend layer: the pluggable numeric engine of the Stream
Processor (paper §3.3 technology-independence, made literal).

Every hot op of the Data Transformer / In-memory Table Updater is expressed
against the ``ComputeBackend`` protocol:

  * ``hash_probe``     — open-addressing probe of the in-memory master cache
                         (the streaming join of §3.1.2),
  * ``transform_block``— the fused fact-grain transform: both cache probes +
                         interval intersection (Fig. 3) + OEE KPI math (§4),
                         returning a device-resident ``FactBlock`` (and,
                         with ``n_units``, the per-unit KPI rollup in the
                         SAME dispatch — ``transform_and_rollup``),
  * ``transform``      — host-convenience wrapper: ``transform_block`` +
                         an immediate ``FactBlock.to_host()``,
  * ``segment_reduce`` — per-equipment KPI rollup of a fact block (the OLAP
                         aggregate the Target Database Updater feeds; the
                         hot path gets this fused into the transform
                         dispatch via ``transform_and_rollup``),
  * ``fold_segments``  — the serving layer's incremental-view delta fold:
                         fused multi-statistic segmented aggregate
                         (count + sum + min + max per segment per value
                         lane) of one fact delta, in ONE dispatch per
                         block, segment-COMPACTED: the tree folds only the
                         delta's live segments and scatters into the
                         packed table (``repro.serving.engine`` folds
                         these into materialized report views),
  * ``fold_segments_scan`` — the same delta fold expressed as a
                         ``jax.lax.associative_scan`` over bit-reversed
                         rows: BITWISE-identical to the halving tree (the
                         bit-reversal permutation makes the scan's
                         adjacent-pair combine order equal the tree's
                         stride-halving order), kept as a parity-proven
                         alternative (measured slower than the unrolled
                         tree on CPU hosts — see docs/BENCHMARKS.md),
  * ``batch_gather_stats`` — the batched read path's point-query op: ONE
                         gather dispatch answers a whole batch of
                         per-segment stat lookups (count/sum/min/max +
                         means) against a packed view table,
  * ``prefix_fold``    — the windowed read path's cumulative fold: all S
                         window prefixes of a packed view table combined
                         in one O(log S)-depth associative scan
                         (bitwise-equal to halving-tree-folded pow2
                         blocks chained in block order — the same
                         association ``_fold_blocks`` uses; oracle:
                         ``prefix_fold_reference``).

Three registered implementations:

  ``numpy``   pure-host reference (no jit, no device) — the oracle,
  ``jax``     jitted jnp (XLA; CPU/GPU/TPU via jax.default_backend),
  ``pallas``  TPU Pallas kernels (``hash_join`` / ``segment_kpi``),
              compiled on TPU, interpret-mode on CPU.

Selection order: explicit name > ``ETLConfig.backend`` > the
``DODETL_BACKEND`` environment variable > ``"jax"``. A fourth backend is a
subclass + ``@register_backend("name")`` — see ARCHITECTURE.md.

Protocol boundaries: inputs are host numpy arrays; ``transform_block``
returns an opaque ``FactBlock`` that stays device-resident (no blocking
``np.asarray`` sync) until ``to_host()`` is called at the warehouse-load
boundary, so XLA's async dispatch overlaps device compute with the load
stage's host work. The jax/pallas backends mirror the cache to device
lazily via ``InMemoryTable.device_state`` (component-dirty tracked, so
steady-state snapshots re-upload nothing).

Instrumentation: every backend instance counts ``op_dispatches`` (device
dispatch groups issued) and ``host_syncs`` (blocking device→host
materializations). The counters are advisory/single-threaded — the
dispatch-overhead benchmark and the tier-1 dispatch-count tests read them.
"""
from __future__ import annotations

import itertools
import os
from typing import Dict, Optional, Tuple, Type, Union

import numpy as np

from repro.core.records import PAYLOAD_WIDTH
from repro.observability.registry import global_registry

EPS = 1e-6
DEFAULT_BACKEND = "jax"
ENV_VAR = "DODETL_BACKEND"

# backends are process singletons (get_backend) but tests construct ad-hoc
# instances too; each instance gets its own registry shard so resets stay
# per-instance while the merged read path sums per-backend process totals
_BACKEND_SEQ = itertools.count()

# fact layout produced by every backend's ``transform`` (keep in sync with
# repro.core.transformer.FACT_COLUMNS)
N_FACT = 10
KPI_LANES = 5   # availability, performance, quality, oee, count

# ------------------------------------------------------------- fold layout
# ``fold_segments`` packs its fused statistics as one [n_segments, W] f32
# table, W = 1 + 3 * n_lanes: [count | sums(L) | mins(L) | maxs(L)].
# Empty segments carry count 0, sum 0, min +inf, max -inf — the identity
# elements, so folds combine associatively lane-by-lane.
FOLD_BLOCK = 2048   # max rows per fold dispatch (bounds the [B, S, L] temp)


def fold_width(n_lanes: int) -> int:
    return 1 + 3 * n_lanes


def empty_fold_state(n_segments: int, n_lanes: int) -> np.ndarray:
    """The fold identity: what every view's aggregate state starts as."""
    out = np.zeros((n_segments, fold_width(n_lanes)), np.float32)
    out[:, 1 + n_lanes:1 + 2 * n_lanes] = np.inf
    out[:, 1 + 2 * n_lanes:] = -np.inf
    return out


def combine_fold(state: np.ndarray, delta: np.ndarray) -> np.ndarray:
    """Associative combine of two packed fold tables (host, elementwise —
    the same ops in every backend, so combining is bitwise deterministic).
    Returns a NEW array; never mutates either input (the serving layer's
    published epochs are immutable)."""
    L = (state.shape[1] - 1) // 3
    out = np.empty_like(state)
    out[:, :1 + L] = state[:, :1 + L] + delta[:, :1 + L]          # count+sum
    out[:, 1 + L:1 + 2 * L] = np.minimum(state[:, 1 + L:1 + 2 * L],
                                         delta[:, 1 + L:1 + 2 * L])
    out[:, 1 + 2 * L:] = np.maximum(state[:, 1 + 2 * L:],
                                    delta[:, 1 + 2 * L:])
    return out


def _fold_tree_np(seg: np.ndarray, vals: np.ndarray,
                  n_segments: int) -> np.ndarray:
    """Reference fold of ONE padded power-of-two block: a fixed pairwise
    halving tree over the one-hot-masked lanes. Every op is an exact or
    correctly-rounded IEEE elementwise op applied in a shape-determined
    order, so the jax twin (same tree) produces bitwise-identical results —
    the property behind the serving layer's byte-identical
    incremental-vs-recompute equivalence tests. Rows with seg outside
    [0, n_segments) (including the -1 padding) contribute the identity."""
    onehot = seg[:, None] == np.arange(n_segments, dtype=seg.dtype)[None, :]
    oh = onehot.astype(np.float32)                       # [B, S]
    cnt = oh
    sums = oh[:, :, None] * vals[:, None, :]             # exact: x*{0,1}
    mins = np.where(onehot[:, :, None], vals[:, None, :],
                    np.float32(np.inf))
    maxs = np.where(onehot[:, :, None], vals[:, None, :],
                    np.float32(-np.inf))
    while cnt.shape[0] > 1:
        h = cnt.shape[0] // 2
        cnt = cnt[:h] + cnt[h:]
        sums = sums[:h] + sums[h:]
        mins = np.minimum(mins[:h], mins[h:])
        maxs = np.maximum(maxs[:h], maxs[h:])
    return np.concatenate([cnt[0][:, None], sums[0], mins[0], maxs[0]],
                          axis=1)


def _fold_blocks(seg: np.ndarray, vals: np.ndarray, n_segments: int,
                 tree) -> np.ndarray:
    """Shared delta driver, SEGMENT-COMPACTED: ``np.unique`` the delta's
    live segment ids, remap them to a dense [0, n_active) range, fold the
    halving tree over ``[block, n_active, lanes]`` instead of
    ``[block, n_segments, lanes]``, then scatter the folded columns back
    into the packed ``[n_segments, W]`` table. A delta touching 2 of 2048
    segments folds a 2-wide tree, not a 2048-wide one.

    Bitwise contract unchanged: the tree is elementwise per segment column
    (a segment's fold never reads another segment's lanes), so dropping
    inactive columns and scattering afterwards reproduces the uncompacted
    tree's per-segment op order EXACTLY — the numpy==jax bitwise
    determinism and ``rebuild()`` byte-identity properties survive
    (asserted against an uncompacted reference in tests/test_serving.py).

    Chunking as before: <= FOLD_BLOCK row blocks, each padded to a power of
    two with seg = -1 identity rows, partials chained in block order (host
    combine). The active-column count is padded to a power of two (>= 8,
    capped at n_segments) so jitted trees compile once per
    (rows, columns) bucket, not once per distinct delta sparsity."""
    seg = np.asarray(seg, np.int64)
    vals = np.asarray(vals, np.float32)
    if vals.ndim == 1:
        vals = vals[:, None]
    n, L = vals.shape
    out = empty_fold_state(n_segments, L)
    if n == 0:
        return out
    in_range = (seg >= 0) & (seg < n_segments)
    live = np.unique(seg[in_range])
    n_active = len(live)
    if n_active == 0:
        return out                       # nothing but identity rows
    n_fold = min(n_segments, max(8, 1 << (n_active - 1).bit_length()))
    # rows outside [0, n_segments) become -1 (identity), live ids become
    # their rank in the sorted live array — the compact column index.
    # Dense deltas (every segment live) skip the remap: rank == id.
    if n_active == n_segments:
        cseg = seg if in_range.all() else np.where(in_range, seg, -1)
    else:
        cseg = np.where(in_range, np.searchsorted(live, seg), -1)
    acc = empty_fold_state(n_fold, L)
    for lo in range(0, n, FOLD_BLOCK):
        s = cseg[lo:lo + FOLD_BLOCK]
        v = vals[lo:lo + FOLD_BLOCK]
        m = len(s)
        bucket = max(8, 1 << (m - 1).bit_length())
        if bucket != m:
            s = np.concatenate([s, np.full(bucket - m, -1, np.int64)])
            v = np.concatenate([v, np.zeros((bucket - m, L), np.float32)])
        acc = combine_fold(acc, tree(s, v, n_fold))
    out[live] = acc[:n_active]           # scatter into the packed table
    return out


_BITREV_CACHE: Dict[int, np.ndarray] = {}


def bitrev_permutation(n: int) -> np.ndarray:
    """Bit-reversal permutation of [0, n) for power-of-two ``n``.

    The load-bearing identity of the scan fold: the halving tree
    (``x[:h] ⊕ x[h:]`` repeated) applied to ``x`` combines exactly the
    same operand pairs, at the same tree levels, as the adjacent-pair
    tree (``x[0::2] ⊕ x[1::2]`` repeated) applied to ``x[bitrev]`` — and
    the adjacent-pair tree is precisely the reduction
    ``jax.lax.associative_scan`` computes for its last output element.
    Permuting rows first therefore makes the scan's reduction BITWISE
    equal to ``_fold_tree_np``'s halving tree."""
    if n & (n - 1):
        raise ValueError(f"bitrev needs a power of two, got {n}")
    cached = _BITREV_CACHE.get(n)
    if cached is None:
        bits = (n - 1).bit_length()
        idx = np.arange(n, dtype=np.int64)
        rev = np.zeros(n, np.int64)
        for b in range(bits):
            rev |= ((idx >> b) & 1) << (bits - 1 - b)
        rev.flags.writeable = False
        _BITREV_CACHE[n] = cached = rev
    return cached


def _fold_tree_scan_np(seg: np.ndarray, vals: np.ndarray,
                       n_segments: int) -> np.ndarray:
    """Scan-order twin of ``_fold_tree_np``: bit-reverse the (padded,
    power-of-two) rows, then reduce ADJACENT pairs — the combine order of
    ``jax.lax.associative_scan``'s final element. Bitwise-identical to the
    halving tree (see ``bitrev_permutation``), so it plugs into
    ``_fold_blocks`` under the same determinism contract."""
    rev = bitrev_permutation(len(seg))
    seg = seg[rev]
    vals = vals[rev]
    onehot = seg[:, None] == np.arange(n_segments, dtype=seg.dtype)[None, :]
    oh = onehot.astype(np.float32)
    cnt = oh
    sums = oh[:, :, None] * vals[:, None, :]
    mins = np.where(onehot[:, :, None], vals[:, None, :], np.float32(np.inf))
    maxs = np.where(onehot[:, :, None], vals[:, None, :], np.float32(-np.inf))
    while cnt.shape[0] > 1:
        cnt = cnt[0::2] + cnt[1::2]
        sums = sums[0::2] + sums[1::2]
        mins = np.minimum(mins[0::2], mins[1::2])
        maxs = np.maximum(maxs[0::2], maxs[1::2])
    return np.concatenate([cnt[0][:, None], sums[0], mins[0], maxs[0]],
                          axis=1)


# ------------------------------------------------- batched read-path helpers
def gather_width(n_lanes: int) -> int:
    """Row width of ``batch_gather_stats`` output:
    [count | sums(L) | mins(L) | maxs(L) | means(L)]."""
    return 1 + 4 * n_lanes


def _gather_stats_np(table: np.ndarray, idx: np.ndarray) -> np.ndarray:
    table = np.asarray(table, np.float32)
    idx = np.asarray(idx, np.int64)
    L = (table.shape[1] - 1) // 3
    t = table[idx]                                   # [B, 1 + 3L]
    cnt = t[:, :1]
    with np.errstate(invalid="ignore", divide="ignore"):
        means = np.where(cnt > 0, t[:, 1:1 + L] / cnt,
                         np.float32(np.nan))
    return np.concatenate([t, means], axis=1)        # [B, 1 + 4L]


def _combine_packed_np(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """Vectorized ``combine_fold`` over leading axes (rows are packed
    [1 + 3L] fold vectors; the lane split is the last axis)."""
    L = (a.shape[-1] - 1) // 3
    return np.concatenate([
        a[..., :1 + L] + b[..., :1 + L],
        np.minimum(a[..., 1 + L:1 + 2 * L], b[..., 1 + L:1 + 2 * L]),
        np.maximum(a[..., 1 + 2 * L:], b[..., 1 + 2 * L:])], axis=-1)


def _assoc_scan_np(x: np.ndarray) -> np.ndarray:
    """Host twin of ``jax.lax.associative_scan`` (inclusive, axis 0) over
    packed fold rows — the SAME odd/even recursion, so results are bitwise
    identical to the jitted scan. Callers pad to a power of two first
    (every recursion level then stays even)."""
    n = x.shape[0]
    if n < 2:
        return x.copy()
    reduced = _combine_packed_np(x[0::2], x[1::2])
    odd = _assoc_scan_np(reduced)
    if n % 2 == 0:
        even = _combine_packed_np(odd[:-1], x[2::2])
    else:
        even = _combine_packed_np(odd, x[2::2])
    out = np.empty_like(x)
    out[0] = x[0]
    out[1::2] = odd
    out[2::2] = even
    return out


def prefix_fold_reference(table: np.ndarray) -> np.ndarray:
    """Recompute-from-scratch oracle for ``prefix_fold``: window ``w``'s
    cumulative aggregate built the way ``_fold_blocks`` chains blocks —
    split rows [0, w] into the power-of-two blocks of the binary
    decomposition of w+1 (largest first), reduce each block with the
    balanced adjacent-pair tree, and left-chain the block partials with
    the associative combine. ``jax.lax.associative_scan``'s inclusive
    prefixes use exactly this association, so ``prefix_fold`` must match
    BITWISE (asserted in tests and the scan-fold benchmark). O(S²) — an
    oracle, not a serving path."""
    table = np.asarray(table, np.float32)
    S = len(table)
    out = np.empty_like(table)
    for w in range(S):
        n = w + 1
        acc = None
        lo = 0
        for b in reversed(range(n.bit_length())):
            if (n >> b) & 1:
                blk = table[lo:lo + (1 << b)]
                while len(blk) > 1:          # balanced adjacent-pair tree
                    blk = _combine_packed_np(blk[0::2], blk[1::2])
                acc = blk[0] if acc is None \
                    else _combine_packed_np(acc, blk[0])
                lo += 1 << b
        out[w] = acc
    return out


def _prefix_fold_np(table: np.ndarray) -> np.ndarray:
    """Numpy ``prefix_fold``: pad the window axis to a power of two with
    fold-identity rows (an inclusive scan's prefix [w] never reads rows
    past w, so padding is invisible), run the associative-scan twin,
    slice. One pass, O(S log S) combines — vs O(S²) for S independent
    per-window refolds."""
    table = np.asarray(table, np.float32)
    S, W = table.shape
    if S == 0:
        return table.copy()
    L = (W - 1) // 3
    m = 1 << (S - 1).bit_length()
    if m != S:
        pad = np.broadcast_to(empty_fold_state(1, L), (m - S, W))
        table = np.concatenate([table, pad])
    return _assoc_scan_np(table)[:S]


class FactBlock:
    """Opaque handle to ONE transform dispatch's results — the unit of the
    device-resident hot path.

    For device backends (jax/pallas) ``facts``/``found`` (and the optional
    fused per-unit KPI ``rollup``) are device arrays: creating the block
    does NOT block on the dispatch, so the transform stage can hand the
    block downstream while XLA is still computing. ``start_host_copy()``
    enqueues the device→host copies asynchronously behind the compute;
    ``to_host()`` — called once, at the warehouse-load boundary —
    materializes and caches the host arrays (the step's single
    host↔device round trip, counted in ``backend.host_syncs``). For the
    numpy backend the arrays are already host-resident and ``to_host()``
    is free.

    ``n`` is the logical row count; device arrays may be padded to a
    power-of-two bucket, and ``to_host()`` slices the pad rows off."""

    __slots__ = ("_backend", "_facts", "_found", "_rollup", "n", "_host",
                 "_rollup_host")

    def __init__(self, backend: "ComputeBackend", facts, found, n: int,
                 rollup=None):
        self._backend = backend
        self._facts = facts
        self._found = found
        self._rollup = rollup
        self.n = int(n)
        self._host: Optional[Tuple[np.ndarray, np.ndarray]] = None
        self._rollup_host: Optional[np.ndarray] = None

    def __len__(self) -> int:
        return self.n

    @property
    def backend(self) -> "ComputeBackend":
        return self._backend

    @property
    def device(self) -> bool:
        """True while the block's arrays live on device (not yet synced)."""
        return self._backend.device and self._host is None

    def start_host_copy(self) -> "FactBlock":
        """Enqueue the D2H copies behind the in-flight device compute
        WITHOUT blocking, so the copy overlaps downstream host work and the
        eventual ``to_host()`` finds the bytes already (or nearly) landed.
        No-op for host backends and already-materialized blocks."""
        if self._backend.device and self._host is None:
            for arr in (self._facts, self._found, self._rollup):
                start = getattr(arr, "copy_to_host_async", None)
                if start is not None:
                    start()
        return self

    def to_host(self) -> Tuple[np.ndarray, np.ndarray]:
        """Materialize (facts [n, N_FACT] f32, found [n] bool) on host.
        The FIRST call on a device block is the hot path's one blocking
        sync (counted in ``backend.host_syncs``); repeats are cached."""
        if self._host is None:
            if self._backend.device:
                self._backend.host_syncs += 1
            facts = np.asarray(self._facts)[:self.n]
            found = np.asarray(self._found)[:self.n]
            if self._rollup is not None and self._rollup_host is None:
                # tiny [n_units, KPI_LANES]; rides the same sync window
                self._rollup_host = np.asarray(self._rollup)
            self._host = (facts, found)
        return self._host

    def rollup_host(self) -> Optional[np.ndarray]:
        """The fused per-unit KPI rollup [n_units, KPI_LANES] (host), or
        None when the block was dispatched without one. After ``to_host``
        this is a cached tiny copy accounted with the block's single
        sync; called BEFORE ``to_host`` on a device block it must block
        on the whole dispatch, so it counts its own sync — the counter
        contract the tier-1 tests and CI dispatch gate pin stays honest
        under call reordering."""
        if self._rollup is None:
            return None
        if self._rollup_host is None:
            if self._backend.device and self._host is None:
                self._backend.host_syncs += 1
            self._rollup_host = np.asarray(self._rollup)
        return self._rollup_host


class ComputeBackend:
    """Protocol + shared helpers. Subclass and register to add a backend."""

    name: str = "abstract"
    device: bool = False     # True: wants the cache's device-mirrored state

    def __init__(self):
        # dispatch instrumentation lives on the process-wide metrics
        # registry (one read path with every other pipeline signal), one
        # shard per backend INSTANCE so per-instance counts/resets — the
        # contract the dispatch-count tests pin — are unchanged; the
        # registry merge sums instances into per-backend process totals
        # (``backend.<name>.op_dispatches``). The ``op_dispatches`` /
        # ``host_syncs`` properties keep the historical int-attribute
        # surface byte-for-byte.
        shard = global_registry().shard(
            f"backend.{self.name}#{next(_BACKEND_SEQ)}")
        self.metrics = shard
        self._op_dispatches = shard.counter(
            f"backend.{self.name}.op_dispatches")
        self._host_syncs = shard.counter(f"backend.{self.name}.host_syncs")

    @property
    def op_dispatches(self) -> int:
        """Device dispatch groups issued (single-threaded use: the
        dispatch benchmark + tier-1 dispatch-count tests)."""
        return self._op_dispatches.value

    @op_dispatches.setter
    def op_dispatches(self, v: int) -> None:
        self._op_dispatches.value = v

    @property
    def host_syncs(self) -> int:
        """Blocking device->host materializations."""
        return self._host_syncs.value

    @host_syncs.setter
    def host_syncs(self, v: int) -> None:
        self._host_syncs.value = v

    def reset_stats(self) -> None:
        self.op_dispatches = 0
        self.host_syncs = 0

    # ------------------------------------------------------------- protocol
    def hash_probe(self, query_keys, keys_tbl, vals_tbl, txn_tbl
                   ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """Linear-probe ``query_keys`` against an open-addressing table.
        Returns host (values [n, W] f32, found [n] bool, txn [n])."""
        raise NotImplementedError

    def transform_block(self, payload: np.ndarray, *caches,
                        schema: str = "steelworks", join_depth: int = 1,
                        n_units: Optional[int] = None) -> FactBlock:
        """Fused transform of one block of operational payloads against
        the worker's caches (``InMemoryTable`` objects or their snapshots, in
        the configuration's ``cached_tables`` order), returned as a
        device-resident ``FactBlock`` (NO host sync). The configuration's
        ``schema`` picks the body:

        * ``steelworks``: production payloads [n, 8] f32 against the
          equipment and quality caches -> OEE facts [n, 10]. With
          ``n_units`` set, the SAME dispatch also produces the per-unit
          KPI rollup (``FactBlock.rollup_host()`` — ``segment_reduce``
          semantics over the block's valid facts). ``join_depth > 1``
          replays the probe chain (§4.1.4 complexity knob — numerically a
          no-op, cost is the point);
        * ``tpcdi-dimtrade``: Trade CDC payloads [n, 16] i32 against the
          DimAccount (point-in-time) and DimSecurity caches -> DimTrade
          rows [n, 21] i32 (``repro.core.dimtrade``)."""
        raise NotImplementedError

    def transform(self, payload: np.ndarray, *caches, join_depth: int = 1,
                  schema: str = "steelworks"
                  ) -> Tuple[np.ndarray, np.ndarray]:
        """Host-convenience transform: ``transform_block`` + an immediate
        ``to_host()``. Returns host (rows, found [n] bool). The
        device-resident hot path uses ``transform_block`` directly and
        defers the sync to the warehouse-load boundary."""
        return self.transform_block(payload, *caches, schema=schema,
                                    join_depth=join_depth).to_host()

    def transform_and_rollup(self, prod: np.ndarray, *caches, n_units: int,
                             join_depth: int = 1) -> FactBlock:
        """Fused steelworks transform + per-unit KPI rollup in ONE
        dispatch: the block's facts/found plus ``rollup_host()`` ==
        ``segment_reduce(facts[found], n_units)`` (parity-tested like the
        other ops). The hot path's replacement for the separate
        transform-then-rollup round trips."""
        return self.transform_block(prod, *caches, join_depth=join_depth,
                                    n_units=n_units)

    def segment_reduce(self, facts: np.ndarray, n_units: int) -> np.ndarray:
        """Per-equipment KPI rollup of a fact block: sums
        [availability, performance, quality, oee, count] over valid facts.
        Returns host [n_units, KPI_LANES] f32."""
        raise NotImplementedError

    def fold_segments(self, seg_ids: np.ndarray, values: np.ndarray,
                      n_segments: int) -> np.ndarray:
        """Fused multi-statistic delta fold for incremental materialized
        views: per segment, count + sum + min + max of every value lane in
        one dispatch per block, segment-compacted (see ``_fold_blocks``).
        ``seg_ids`` [n] int, ``values`` [n, L] f32; rows with seg outside
        [0, n_segments) contribute nothing. Returns the packed host table
        [n_segments, 1 + 3L] (see ``fold_width``)."""
        raise NotImplementedError

    def fold_segments_scan(self, seg_ids: np.ndarray, values: np.ndarray,
                           n_segments: int) -> np.ndarray:
        """``fold_segments`` with the per-block reduction expressed as an
        associative scan over bit-reversed rows instead of the unrolled
        halving tree (O(log n) combine depth either way; the scan form is
        the one scan-capable hardware pipelines). BITWISE-identical output
        to ``fold_segments`` — the bit-reversal permutation aligns the
        scan's adjacent-pair combine order with the tree's stride-halving
        order (see ``bitrev_permutation``). Measured slower than the
        unrolled tree on CPU hosts (XLA does not dead-code the scan's
        unused prefixes), so the tree stays the default write-side fold;
        this op is the parity-proven alternative and the form the
        windowed read path's ``prefix_fold`` shares its association
        with."""
        raise NotImplementedError

    def batch_gather_stats(self, table: np.ndarray,
                           seg_ids: np.ndarray) -> np.ndarray:
        """Batched point-query op of the read path: gather ``B`` segment
        rows from a packed ``[S, 1 + 3L]`` fold table and derive lane
        means, in ONE dispatch. ``seg_ids`` [B] int in [0, S). Returns
        host ``[B, 1 + 4L]`` f32: [count | sums | mins | maxs | means],
        means NaN where count == 0 (see ``gather_width``). Bitwise
        deterministic: the mean is the same single correctly-rounded f32
        divide the per-query path performs."""
        raise NotImplementedError

    def prefix_fold(self, table: np.ndarray) -> np.ndarray:
        """Cumulative windowed fold of the read path: inclusive running
        combine of a packed ``[S, 1 + 3L]`` view table along the window
        axis — row ``w`` of the result aggregates windows [0, w]. ONE
        O(log S)-depth associative scan answers every window prefix at
        once, replacing S independent per-window refolds (the S ≳ 128
        win). Bitwise-deterministic across numpy/jax and equal to
        ``prefix_fold_reference``. Returns host ``[S, 1 + 3L]`` f32."""
        raise NotImplementedError

    # ------------------------------------------------- device-mesh extension
    mesh = None   # optional 1-D device mesh (axis "shards") — see set_mesh

    def set_mesh(self, mesh) -> None:
        """Attach a 1-D device mesh (single axis, one device per serving
        shard) so ``fold_segments_sharded`` may run each shard's fold on
        its own device via ``shard_map``. ``None`` detaches. Backends
        without a device plane keep the host reference path; attaching a
        mesh never changes WHAT is computed (bitwise contract below)."""
        self.mesh = mesh

    def fold_segments_sharded(self, seg_ids: np.ndarray, values: np.ndarray,
                              n_segments: int, owners: np.ndarray,
                              n_shards: int) -> np.ndarray:
        """Shard-local delta folds for the sharded serving plane
        (``repro.runtime.shard_plane``): shard ``k`` folds the FULL delta
        with every segment it does not own masked to the -1 identity, so
        nothing crosses shards on the write path. ``owners`` [n_segments]
        int maps segment id -> owning shard. Returns the stacked host
        tables ``[n_shards, n_segments, 1 + 3L]``.

        Bitwise contract: the fold tree is elementwise per segment column
        (a segment's fold never reads another segment's lanes — the
        ``_fold_blocks`` compaction argument), so shard ``k``'s owned
        columns are bitwise identical to the single-device
        ``fold_segments`` columns, and its foreign columns are exactly
        the ``empty_fold_state`` identity. Reference implementation: one
        masked ``fold_segments`` per shard on the host."""
        seg = np.asarray(seg_ids, np.int64)
        owners = np.asarray(owners, np.int64)
        vals = np.asarray(values, np.float32)
        if vals.ndim == 1:
            vals = vals[:, None]
        in_range = (seg >= 0) & (seg < n_segments)
        own = np.where(in_range, owners[np.clip(seg, 0, n_segments - 1)], -1)
        return np.stack([
            self.fold_segments(np.where(own == k, seg, -1), vals, n_segments)
            for k in range(n_shards)])

    # -------------------------------------------------------------- helpers
    @staticmethod
    def _pad_bucket(prod: np.ndarray, floor: int = 1,
                    mutable: bool = False) -> np.ndarray:
        """Pad a payload block to a power-of-two bucket (>= floor) so jitted
        dispatch compiles once per bucket, not once per arrival size.

        When ``n`` already fills the bucket the input is returned as-is
        (zero-copy) — callers that WRITE into the padded block must pass
        ``mutable=True``, which guarantees the result never aliases the
        caller's array (a power-of-two-sized input used to come back
        aliased, and ``PallasBackend.segment_reduce`` scribbled on its
        caller's facts — see tests/test_backends.py regression)."""
        n = len(prod)
        bucket = max(floor, 1 << (n - 1).bit_length())
        if bucket == n:
            return prod.copy() if mutable else prod
        padrow = np.full((bucket - n, prod.shape[1]), -1, prod.dtype)
        return np.concatenate([prod, padrow])


_REGISTRY: Dict[str, Type[ComputeBackend]] = {}
_INSTANCES: Dict[str, ComputeBackend] = {}


def register_backend(name: str):
    def deco(cls: Type[ComputeBackend]) -> Type[ComputeBackend]:
        cls.name = name
        _REGISTRY[name] = cls
        return cls
    return deco


def available_backends() -> Tuple[str, ...]:
    return tuple(sorted(_REGISTRY))


def resolve_backend_name(name: Optional[str] = None) -> str:
    return name or os.environ.get(ENV_VAR) or DEFAULT_BACKEND


def get_backend(name: Union[str, ComputeBackend, None] = None
                ) -> ComputeBackend:
    """Resolve a backend instance (singletons per name). Accepts an already
    constructed backend, a registered name, None (config/env default)."""
    if isinstance(name, ComputeBackend):
        return name
    resolved = resolve_backend_name(name)
    if resolved not in _REGISTRY:
        raise KeyError(f"unknown backend {resolved!r}; "
                       f"registered: {available_backends()}")
    if resolved not in _INSTANCES:
        _INSTANCES[resolved] = _REGISTRY[resolved]()
    return _INSTANCES[resolved]


# =========================================================== numpy backend
def _hash_probe_np(query_keys, keys_tbl, vals_tbl, txn_tbl):
    from repro.core.cache import MAX_PROBES, hash32_np
    keys_tbl = np.asarray(keys_tbl)
    vals_tbl = np.asarray(vals_tbl)
    txn_tbl = np.asarray(txn_tbl)
    n_slots = keys_tbl.shape[0]
    q = (np.asarray(query_keys).astype(np.int64)
         & 0xFFFFFFFF).astype(np.int32)
    h = (hash32_np(q) % np.uint32(n_slots)).astype(np.int64)
    n = len(q)
    done = np.zeros(n, bool)
    found = np.zeros(n, bool)
    val = np.zeros((n, vals_tbl.shape[1]), vals_tbl.dtype)
    txn = np.zeros(n, txn_tbl.dtype)
    for p in range(MAX_PROBES):
        cand = (h + p) % n_slots
        k = keys_tbl[cand]
        hit = (k == q) & ~done
        empty = (k == -1) & ~done
        if hit.any():
            val[hit] = vals_tbl[cand[hit]]
            txn[hit] = txn_tbl[cand[hit]]
            found |= hit
        done |= hit | empty
        if done.all():
            break
    return val, found, txn


def _lookup_asof_np(query_keys, query_times, keys_tbl, vals_tbl,
                    asof_lane: int):
    """Host twin of ``cache.lookup_asof``: per key, the version with the
    latest EffectiveDate not after its query time. Returns (values [n, W],
    found [n] bool)."""
    from repro.core.cache import MAX_PROBES, hash32_np
    keys_tbl = np.asarray(keys_tbl)
    vals_tbl = np.asarray(vals_tbl)
    n_slots = keys_tbl.shape[0]
    q = (np.asarray(query_keys).astype(np.int64)
         & 0xFFFFFFFF).astype(np.int32)
    t = np.asarray(query_times).astype(np.int64)
    h = (hash32_np(q) % np.uint32(n_slots)).astype(np.int64)
    done = np.zeros(len(q), bool)
    idx = np.full(len(q), -1, np.int64)
    best = np.zeros(len(q), np.int64)
    for p in range(MAX_PROBES):
        cand = (h + p) % n_slots
        k = keys_tbl[cand]
        eff = vals_tbl[cand, asof_lane].astype(np.int64)
        better = (k == q) & (eff <= t) & ((idx < 0) | (eff > best)) & ~done
        idx = np.where(better, cand, idx)
        best = np.where(better, eff, best)
        done |= k == -1
        if done.all():
            break
    found = idx >= 0
    val = np.where(found[:, None], vals_tbl[np.maximum(idx, 0)], 0)
    return val.astype(vals_tbl.dtype), found


def _segment_reduce_np(facts: np.ndarray, n_units: int) -> np.ndarray:
    facts = np.asarray(facts, np.float32)
    agg = np.zeros((n_units, KPI_LANES), np.float32)
    if not len(facts):
        return agg
    unit = facts[:, 0].astype(np.int64)
    # drop invalid facts AND out-of-range units, matching the jax/pallas
    # behavior (segment_sum / one-hot ignore ids outside [0, n_units))
    keep = (facts[:, 9] > 0.5) & (unit >= 0) & (unit < n_units)
    kpis = np.concatenate(
        [facts[keep, 3:7],
         np.ones((int(keep.sum()), 1), np.float32)], axis=-1)
    np.add.at(agg, unit[keep], kpis)
    return agg


@register_backend("numpy")
class NumpyBackend(ComputeBackend):
    """Pure-host reference. Mirrors the jitted math op-for-op in float32 so
    parity with jax/pallas holds to ~1e-6; the correctness oracle and the
    zero-dependency fallback. ``FactBlock``s are host-resident from birth
    (``to_host`` is free and counts no sync)."""

    device = False

    def hash_probe(self, query_keys, keys_tbl, vals_tbl, txn_tbl):
        self.op_dispatches += 1
        return _hash_probe_np(query_keys, keys_tbl, vals_tbl, txn_tbl)

    def transform_block(self, prod, *caches, schema="steelworks",
                        join_depth=1, n_units=None):
        if schema == "tpcdi-dimtrade":
            from repro.core.dimtrade import dimtrade_np
            acct, sec = caches
            rows, found = dimtrade_np(prod, (acct.keys, acct.values),
                                      (sec.keys, sec.values, sec.txn),
                                      acct.asof_lane)
            self.op_dispatches += 1
            return FactBlock(self, rows, found, len(rows))
        equipment, quality = caches
        prod = np.asarray(prod, np.float32)
        eq_state = (equipment.keys, equipment.values, equipment.txn)
        q_state = (quality.keys, quality.values, quality.txn)
        equip_id = prod[:, 1].astype(np.int64)
        prod_id = prod[:, 0].astype(np.int64)
        eq_rows, eq_found, _ = _hash_probe_np(equip_id, *eq_state)
        q_rows, q_found, _ = _hash_probe_np(prod_id, *q_state)
        if join_depth > 1:            # flattened hop probe (cost knob;
            mod = max(len(eq_state[0]) // 4, 1)   # numeric no-op)
            hop_keys = ((equip_id[None, :]
                         + np.arange(1, join_depth)[:, None]) % mod)
            _hash_probe_np(hop_keys.reshape(-1), *eq_state)
        found = eq_found & q_found
        facts = _kpi_facts_np(prod, eq_rows, q_rows, found)
        rollup = (_segment_reduce_np(facts, n_units)
                  if n_units is not None else None)
        self.op_dispatches += 1       # the whole fused op: one "dispatch"
        return FactBlock(self, facts, found, len(prod), rollup)

    def segment_reduce(self, facts, n_units):
        self.op_dispatches += 1
        return _segment_reduce_np(facts, n_units)

    def fold_segments(self, seg_ids, values, n_segments):
        def tree(s, v, ns):
            self.op_dispatches += 1
            return _fold_tree_np(s, v, ns)
        return _fold_blocks(seg_ids, values, n_segments, tree)

    def fold_segments_scan(self, seg_ids, values, n_segments):
        def tree(s, v, ns):
            self.op_dispatches += 1
            return _fold_tree_scan_np(s, v, ns)
        return _fold_blocks(seg_ids, values, n_segments, tree)

    def batch_gather_stats(self, table, seg_ids):
        idx = np.asarray(seg_ids, np.int64)
        if not len(idx):
            L = (np.asarray(table).shape[1] - 1) // 3
            return np.zeros((0, gather_width(L)), np.float32)
        self.op_dispatches += 1
        return _gather_stats_np(table, idx)

    def prefix_fold(self, table):
        if not len(table):
            return np.asarray(table, np.float32).copy()
        self.op_dispatches += 1
        return _prefix_fold_np(table)


def _kpi_facts_np(prod, eq_rows, q_rows, found) -> np.ndarray:
    """Host twin of ``transformer.transform_kernel``'s KPI math (same op
    order in float32, so results agree with XLA to float rounding)."""
    f = np.float32
    t_start, t_end = prod[:, 3], prod[:, 4]
    qty = prod[:, 5]
    e_start, e_end = eq_rows[:, 3], eq_rows[:, 4]
    status = eq_rows[:, 5]
    max_speed = eq_rows[:, 6]
    planned = eq_rows[:, 7]
    defects, scrap = q_rows[:, 4], q_rows[:, 6]

    inter_lo = np.maximum(t_start, e_start)
    inter_hi = np.minimum(t_end, e_end)
    overlap = np.maximum(inter_hi - inter_lo, f(0.0))
    duration = np.maximum(t_end - t_start, f(EPS))
    seg_on = np.where(status > f(0.5), overlap, f(0.0))
    seg_off = duration - seg_on

    availability = np.clip(seg_on / np.maximum(planned, f(EPS)),
                           f(0.0), f(1.0))
    performance = np.clip(qty / np.maximum(max_speed * duration, f(EPS)),
                          f(0.0), f(1.0))
    good = np.maximum(qty - defects - scrap, f(0.0))
    quality = np.clip(good / np.maximum(qty, f(EPS)), f(0.0), f(1.0))
    oee = availability * performance * quality
    return np.stack([
        prod[:, 1], t_start, t_end, availability, performance, quality, oee,
        seg_on, seg_off, found.astype(np.float32)], axis=-1).astype(np.float32)


# ============================================================= jax backend
@register_backend("jax")
class JaxBackend(ComputeBackend):
    """Jitted jnp path (XLA). The default: one fused dispatch per worker per
    step, power-of-two bucket padding so steady-state recompiles are zero.
    ``transform_block`` returns without waiting on the dispatch — XLA's
    async dispatch runs the compute (and, after ``start_host_copy``, the
    D2H transfer) while the caller's host code keeps moving."""

    device = True

    def hash_probe(self, query_keys, keys_tbl, vals_tbl, txn_tbl):
        import jax.numpy as jnp
        from repro.core.cache import lookup_ref
        vals, found, txn = lookup_ref(
            jnp.asarray(np.asarray(query_keys), jnp.int32),
            keys_tbl, vals_tbl, txn_tbl)
        self.op_dispatches += 1
        self.host_syncs += 1
        return np.asarray(vals), np.asarray(found), np.asarray(txn)

    def transform_block(self, prod, *caches, schema="steelworks",
                        join_depth=1, n_units=None):
        import jax.numpy as jnp
        if schema == "tpcdi-dimtrade":
            from repro.core.dimtrade import dimtrade_transform_kernel
            acct, sec = caches
            prod = np.asarray(prod, np.int32)
            ak, av, at = acct.device_state()
            sk, sv, st = sec.device_state()
            rows, found = dimtrade_transform_kernel(
                jnp.asarray(self._pad_bucket(prod, floor=128)), ak, av, at,
                sk, sv, st, asof_lane=acct.asof_lane)
            self.op_dispatches += 1
            return FactBlock(self, rows, found, len(prod))
        from repro.core.transformer import (transform_kernel,
                                            transform_rollup_kernel)
        equipment, quality = caches
        prod = np.asarray(prod, np.float32)
        n = len(prod)
        padded = jnp.asarray(self._pad_bucket(prod, floor=128))
        eqk, eqv, eqt = equipment.device_state()
        qk, qv, qt = quality.device_state()
        if n_units is None:
            facts, found = transform_kernel(padded, eqk, eqv, eqt,
                                            qk, qv, qt,
                                            join_depth=join_depth)
            rollup = None
        else:
            facts, found, rollup = transform_rollup_kernel(
                padded, eqk, eqv, eqt, qk, qv, qt,
                join_depth=join_depth, n_units=n_units)
        self.op_dispatches += 1       # ONE fused XLA dispatch, zero syncs
        return FactBlock(self, facts, found, n, rollup)

    def segment_reduce(self, facts, n_units):
        import jax.numpy as jnp
        facts = np.asarray(facts, np.float32)
        if not len(facts):
            return np.zeros((n_units, KPI_LANES), np.float32)
        padded = self._pad_bucket(facts, floor=128)  # pads are valid=0 rows
        self.op_dispatches += 1
        self.host_syncs += 1
        return np.asarray(_rollup_jnp(jnp.asarray(padded), n_units))

    def fold_segments(self, seg_ids, values, n_segments):
        # the jitted twin of the numpy halving tree: identical op order on
        # static shapes, so results are BITWISE equal to the numpy backend
        # (asserted by tests/test_serving.py) while the dispatch itself is
        # one fused XLA call per block (over the COMPACTED segment range —
        # see _fold_blocks)
        def tree(s, v, ns):
            import jax.numpy as jnp
            self.op_dispatches += 1
            self.host_syncs += 1
            return np.asarray(_fold_tree_jnp(jnp.asarray(s, jnp.int32),
                                             jnp.asarray(v), ns))
        return _fold_blocks(seg_ids, values, n_segments, tree)

    def fold_segments_scan(self, seg_ids, values, n_segments):
        # same compacted block driver; the per-block reduction is ONE
        # jax.lax.associative_scan over bit-reversed rows — bitwise equal
        # to the halving tree (see bitrev_permutation)
        def tree(s, v, ns):
            import jax.numpy as jnp
            self.op_dispatches += 1
            self.host_syncs += 1
            rev = bitrev_permutation(len(s))
            return np.asarray(_fold_tree_scan_jnp(
                jnp.asarray(s, jnp.int32), jnp.asarray(v),
                jnp.asarray(rev, jnp.int32), ns))
        return _fold_blocks(seg_ids, values, n_segments, tree)

    def batch_gather_stats(self, table, seg_ids):
        import jax.numpy as jnp
        idx = np.asarray(seg_ids, np.int64)
        n = len(idx)
        if not n:
            L = (np.asarray(table).shape[1] - 1) // 3
            return np.zeros((0, gather_width(L)), np.float32)
        # pow2 bucket so jit compiles once per batch-size bucket; pad ids
        # point at row 0 and the pad rows are sliced off after the sync
        bucket = max(8, 1 << (n - 1).bit_length())
        if bucket != n:
            idx = np.concatenate([idx, np.zeros(bucket - n, np.int64)])
        self.op_dispatches += 1
        self.host_syncs += 1
        out = np.asarray(_gather_stats_jnp(
            jnp.asarray(np.asarray(table, np.float32)),
            jnp.asarray(idx, jnp.int32)))
        return out[:n]

    def prefix_fold(self, table):
        import jax.numpy as jnp
        table = np.asarray(table, np.float32)
        S, W = table.shape
        if S == 0:
            return table.copy()
        L = (W - 1) // 3
        m = 1 << (S - 1).bit_length()
        if m != S:           # identity pad: inclusive prefixes never read it
            table = np.concatenate(
                [table, np.broadcast_to(empty_fold_state(1, L), (m - S, W))])
        self.op_dispatches += 1
        self.host_syncs += 1
        return np.asarray(_prefix_fold_jnp(jnp.asarray(table)))[:S]

    def set_mesh(self, mesh):
        super().set_mesh(mesh)
        self._mesh_fold = None if mesh is None else _make_mesh_fold(mesh)

    def fold_segments_sharded(self, seg_ids, values, n_segments, owners,
                              n_shards):
        # mesh path: ONE shard_map dispatch per row block — every device
        # folds the (replicated) block against its own ownership mask,
        # device-local, no collectives. Without a mesh the host reference
        # (one masked fold_segments per shard) computes the same stack; a
        # mesh of the wrong size is a deployment error, never a silent
        # fallback to the host.
        mesh = self.mesh
        if mesh is None:
            return super().fold_segments_sharded(
                seg_ids, values, n_segments, owners, n_shards)
        if mesh.devices.size != n_shards:
            raise ValueError(
                f"attached mesh has {mesh.devices.size} devices but the "
                f"serving plane has {n_shards} shards")
        import jax.numpy as jnp
        seg = np.asarray(seg_ids, np.int64)
        vals = np.asarray(values, np.float32)
        if vals.ndim == 1:
            vals = vals[:, None]
        n, L = vals.shape
        out = np.stack([empty_fold_state(n_segments, L)] * n_shards)
        if n == 0:
            return out
        own_dev = jnp.asarray(np.asarray(owners, np.int64), jnp.int32)
        # same <= FOLD_BLOCK chunking + pow2 identity padding as
        # _fold_blocks, so per-column op order (and thus bytes) matches
        # the single-device fold exactly; the mesh tree is uncompacted
        # (static [block, n_segments] shape per device), which the
        # compaction contract makes bitwise-invisible
        for lo in range(0, n, FOLD_BLOCK):
            s = seg[lo:lo + FOLD_BLOCK]
            v = vals[lo:lo + FOLD_BLOCK]
            m = len(s)
            bucket = max(8, 1 << (m - 1).bit_length())
            if bucket != m:
                s = np.concatenate([s, np.full(bucket - m, -1, np.int64)])
                v = np.concatenate([v, np.zeros((bucket - m, L), np.float32)])
            self.op_dispatches += 1
            self.host_syncs += 1
            blk = np.asarray(self._mesh_fold(
                jnp.asarray(s, jnp.int32), jnp.asarray(v), own_dev,
                n_segments))
            for k in range(n_shards):
                out[k] = combine_fold(out[k], blk[k])
        return out


def _make_mesh_fold(mesh):
    """Build the jitted ``shard_map`` fold for one mesh: each device runs
    the SAME fixed halving tree as ``_fold_tree_jnp`` over the replicated
    block, with segments not owned by ``axis_index(shards)`` masked to the
    -1 identity first. Per owned segment column the op order is identical
    to the single-device tree, so the stacked [n_shards, S, W] output is
    bitwise the host reference (``ComputeBackend.fold_segments_sharded``).
    The body issues NO collectives — merging shard tables is the read
    path's explicit tree reduce, not the write path's job."""
    import functools

    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    axis = mesh.axis_names[0]

    @functools.partial(jax.jit, static_argnames=("n_segments",))
    def fold(seg, vals, owners, n_segments):
        def device_fold(seg, vals, owners):
            k = jax.lax.axis_index(axis).astype(jnp.int32)
            in_range = (seg >= 0) & (seg < n_segments)
            owner = jnp.where(in_range,
                              owners[jnp.clip(seg, 0, n_segments - 1)],
                              jnp.int32(-1))
            cseg = jnp.where(owner == k, seg, jnp.int32(-1))
            onehot = cseg[:, None] == jnp.arange(n_segments,
                                                 dtype=cseg.dtype)
            oh = onehot.astype(jnp.float32)
            cnt = oh
            sums = oh[:, :, None] * vals[:, None, :]
            mins = jnp.where(onehot[:, :, None], vals[:, None, :], jnp.inf)
            maxs = jnp.where(onehot[:, :, None], vals[:, None, :], -jnp.inf)
            while cnt.shape[0] > 1:
                h = cnt.shape[0] // 2
                cnt = cnt[:h] + cnt[h:]
                sums = sums[:h] + sums[h:]
                mins = jnp.minimum(mins[:h], mins[h:])
                maxs = jnp.maximum(maxs[:h], maxs[h:])
            table = jnp.concatenate(
                [cnt[0][:, None], sums[0], mins[0], maxs[0]], axis=1)
            return table[None]          # [1, S, W] -> stacked [K, S, W]
        return jax.shard_map(device_fold, mesh=mesh,
                             in_specs=(P(), P(), P()),
                             out_specs=P(axis))(seg, vals, owners)

    return fold


_ROLLUP_JIT = None


def _rollup_jnp(facts, n_units: int):
    global _ROLLUP_JIT
    if _ROLLUP_JIT is None:
        import functools

        import jax
        import jax.numpy as jnp

        @functools.partial(jax.jit, static_argnames=("n_units",))
        def rollup(facts, n_units):
            unit = facts[:, 0].astype(jnp.int32)
            valid = facts[:, 9] > 0.5
            kpis = jnp.concatenate(
                [facts[:, 3:7], jnp.ones((facts.shape[0], 1), jnp.float32)],
                axis=-1)
            kpis = jnp.where(valid[:, None], kpis, 0.0)
            # invalid rows route to a trash segment past n_units
            return jax.ops.segment_sum(kpis, jnp.where(valid, unit, n_units),
                                       num_segments=n_units + 1)[:n_units]

        _ROLLUP_JIT = rollup
    return _ROLLUP_JIT(facts, n_units)


_FOLD_JIT = None


def _fold_tree_jnp(seg, vals, n_segments: int):
    """jnp twin of ``_fold_tree_np``: the SAME fixed halving tree of exact
    multiplies and correctly-rounded adds/min/max, so XLA produces bitwise
    the numpy result (the tree is shape-unrolled at trace time — one
    compile per (block, n_segments, lanes) bucket)."""
    global _FOLD_JIT
    if _FOLD_JIT is None:
        import functools

        import jax
        import jax.numpy as jnp

        @functools.partial(jax.jit, static_argnames=("n_segments",))
        def fold(seg, vals, n_segments):
            onehot = seg[:, None] == jnp.arange(n_segments, dtype=seg.dtype)
            oh = onehot.astype(jnp.float32)
            cnt = oh
            sums = oh[:, :, None] * vals[:, None, :]
            mins = jnp.where(onehot[:, :, None], vals[:, None, :], jnp.inf)
            maxs = jnp.where(onehot[:, :, None], vals[:, None, :], -jnp.inf)
            while cnt.shape[0] > 1:
                h = cnt.shape[0] // 2
                cnt = cnt[:h] + cnt[h:]
                sums = sums[:h] + sums[h:]
                mins = jnp.minimum(mins[:h], mins[h:])
                maxs = jnp.maximum(maxs[:h], maxs[h:])
            return jnp.concatenate(
                [cnt[0][:, None], sums[0], mins[0], maxs[0]], axis=1)

        _FOLD_JIT = fold
    return _FOLD_JIT(seg, vals, n_segments)


_SCAN_FOLD_JIT = None


def _fold_tree_scan_jnp(seg, vals, rev, n_segments: int):
    """Scan-form twin of ``_fold_tree_jnp``: one-hot the bit-reversed
    rows, then take the LAST element of an inclusive
    ``jax.lax.associative_scan`` — the scan's reduction combines adjacent
    pairs level by level, which on bit-reversed input is operand-for-
    operand the halving tree, so output is bitwise identical."""
    global _SCAN_FOLD_JIT
    if _SCAN_FOLD_JIT is None:
        import functools

        import jax
        import jax.numpy as jnp

        @functools.partial(jax.jit, static_argnames=("n_segments",))
        def fold(seg, vals, rev, n_segments):
            seg = seg[rev]
            vals = vals[rev]
            onehot = seg[:, None] == jnp.arange(n_segments, dtype=seg.dtype)
            oh = onehot.astype(jnp.float32)
            sums = oh[:, :, None] * vals[:, None, :]
            mins = jnp.where(onehot[:, :, None], vals[:, None, :], jnp.inf)
            maxs = jnp.where(onehot[:, :, None], vals[:, None, :], -jnp.inf)

            def comb(a, b):
                return (a[0] + b[0], a[1] + b[1],
                        jnp.minimum(a[2], b[2]), jnp.maximum(a[3], b[3]))

            c, s, mn, mx = jax.lax.associative_scan(
                comb, (oh, sums, mins, maxs), axis=0)
            return jnp.concatenate(
                [c[-1][:, None], s[-1], mn[-1], mx[-1]], axis=1)

        _SCAN_FOLD_JIT = fold
    return _SCAN_FOLD_JIT(seg, vals, rev, n_segments)


_GATHER_JIT = None


def _gather_stats_jnp(table, idx):
    """Jitted batched gather + means: the mean lane is the same single
    correctly-rounded f32 divide the per-query path performs, so results
    are bitwise equal to ``_gather_stats_np`` (NaN for empty segments)."""
    global _GATHER_JIT
    if _GATHER_JIT is None:
        import jax
        import jax.numpy as jnp

        @jax.jit
        def gather(table, idx):
            L = (table.shape[1] - 1) // 3
            t = table[idx]                           # [B, 1 + 3L]
            cnt = t[:, :1]
            means = jnp.where(cnt > 0, t[:, 1:1 + L] / cnt, jnp.nan)
            return jnp.concatenate([t, means], axis=1)

        _GATHER_JIT = gather
    return _GATHER_JIT(table, idx)


_PREFIX_JIT = None


def _prefix_fold_jnp(table):
    """Jitted inclusive associative scan over packed fold rows (window
    axis). Same odd/even recursion as ``_assoc_scan_np`` — bitwise equal
    to the numpy backend and to ``prefix_fold_reference``."""
    global _PREFIX_JIT
    if _PREFIX_JIT is None:
        import jax
        import jax.numpy as jnp

        @jax.jit
        def pf(table):
            L = (table.shape[1] - 1) // 3

            def comb(a, b):
                return jnp.concatenate([
                    a[..., :1 + L] + b[..., :1 + L],
                    jnp.minimum(a[..., 1 + L:1 + 2 * L],
                                b[..., 1 + L:1 + 2 * L]),
                    jnp.maximum(a[..., 1 + 2 * L:], b[..., 1 + 2 * L:])],
                    axis=-1)

            return jax.lax.associative_scan(comb, table, axis=0)

        _PREFIX_JIT = pf
    return _PREFIX_JIT(table)


# ========================================================== pallas backend
@register_backend("pallas")
class PallasBackend(ComputeBackend):
    """TPU Pallas kernels (``hash_join`` slot-tiled linear probe,
    ``segment_kpi`` fused KPI + rollup). Compiled on a TPU; on the CPU the
    kernels run in interpret mode (``repro.core.device.pallas_interpret``)
    — slow but contract-identical, so parity tests cover the kernel
    path.

    ``transform_block`` issues a constant FEW dispatch groups (two probes,
    the optional hop probe, the fused KPI kernel) rather than jax's single
    one — the per-unit rollup still rides the ``segment_kpi`` kernel's
    fused epilogue, and the block stays device-resident with zero host
    syncs until ``to_host()``."""

    device = True

    def hash_probe(self, query_keys, keys_tbl, vals_tbl, txn_tbl):
        import jax.numpy as jnp
        from repro.kernels.hash_join.ops import hash_join
        vals, found, txn = hash_join(
            jnp.asarray(np.asarray(query_keys), jnp.int32),
            keys_tbl, vals_tbl, txn_tbl)
        self.op_dispatches += 1
        self.host_syncs += 1
        return np.asarray(vals), np.asarray(found), np.asarray(txn)

    def transform_block(self, prod, *caches, schema="steelworks",
                        join_depth=1, n_units=None):
        import jax.numpy as jnp
        from repro.kernels.hash_join.ops import hash_join
        if schema != "steelworks":
            raise NotImplementedError(
                f"the pallas backend has no {schema!r} transform")
        equipment, quality = caches
        from repro.kernels.segment_kpi.ops import segment_kpi
        prod = np.asarray(prod, np.float32)
        n = len(prod)
        padded = jnp.asarray(self._pad_bucket(prod, floor=256))
        eqk, eqv, eqt = equipment.device_state()
        qk, qv, qt = quality.device_state()
        equip_id = padded[:, 1].astype(jnp.int32)
        prod_id = padded[:, 0].astype(jnp.int32)
        eq_rows, eq_found, _ = hash_join(equip_id, eqk, eqv, eqt)
        q_rows, q_found, _ = hash_join(prod_id, qk, qv, qt)
        self.op_dispatches += 2
        if join_depth > 1:            # flattened hop probe (cost knob;
            mod = jnp.int32(max(eqk.shape[0] // 4, 1))  # numeric no-op)
            hop_keys = ((equip_id[None, :]
                         + jnp.arange(1, join_depth,
                                      dtype=jnp.int32)[:, None]) % mod)
            hash_join(hop_keys.reshape(-1), eqk, eqv, eqt)
            self.op_dispatches += 1
        found = eq_found & q_found
        # the kernel derives its valid flag from the joined rows' key lane:
        # mark misses so facts[:, -1] equals the probe's found mask
        eq_rows = eq_rows.at[:, 1].set(
            jnp.where(eq_found, eq_rows[:, 1], -1.0))
        q_rows = q_rows.at[:, 1].set(
            jnp.where(q_found, q_rows[:, 1], -1.0))
        # the fused kernel ALWAYS emits the per-unit aggregate; with
        # n_units requested it IS the rollup (one kernel produces facts +
        # KPI aggregate — the transform_and_rollup contract), otherwise the
        # epilogue is kept minimal and the aggregate dropped
        facts, agg = segment_kpi(padded, eq_rows, q_rows,
                                 n_units=n_units if n_units else 1)
        self.op_dispatches += 1
        rollup = agg if n_units else None
        return FactBlock(self, facts, found, n, rollup)

    def segment_reduce(self, facts, n_units):
        import jax.numpy as jnp
        from repro.kernels.segment_kpi.ops import segment_rollup
        facts = np.asarray(facts, np.float32)
        if not len(facts):
            return np.zeros((n_units, KPI_LANES), np.float32)
        # mutable=True: the pad-marking write below must never land in the
        # caller's array (power-of-two inputs used to come back aliased)
        padded = self._pad_bucket(facts, floor=256, mutable=True)
        padded[len(facts):, 9] = 0.0           # pad rows marked invalid
        self.op_dispatches += 1
        self.host_syncs += 1
        return np.asarray(segment_rollup(jnp.asarray(padded),
                                         n_units=n_units))

    def fold_segments(self, seg_ids, values, n_segments):
        # fused kernel path: one-hot MXU matmul for count+sum, masked lane
        # reductions for min/max (see kernels/segment_kpi), over the
        # compacted segment range. The MXU's reduction order differs from
        # the halving tree, so this backend is parity-checked to ~1e-5,
        # not bitwise (same contract as the other pallas ops).
        def tree(s, v, ns):
            import jax.numpy as jnp
            from repro.kernels.segment_kpi.ops import fold_segments
            packed = jnp.concatenate(
                [jnp.asarray(s, jnp.float32)[:, None], jnp.asarray(v)],
                axis=1)
            self.op_dispatches += 1
            self.host_syncs += 1
            return np.asarray(fold_segments(packed, n_segments=ns))
        return _fold_blocks(seg_ids, values, n_segments, tree)

    def fold_segments_scan(self, seg_ids, values, n_segments):
        # the scan is an XLA structural op — there's no MXU-shaped inner
        # reduction left to kernelize — so this backend shares the jitted
        # scan path (bitwise equal to numpy/jax by the same bit-reversal
        # argument)
        return JaxBackend.fold_segments_scan(self, seg_ids, values,
                                             n_segments)

    def batch_gather_stats(self, table, seg_ids):
        import jax.numpy as jnp
        from repro.kernels.segment_kpi.ops import gather_stats
        idx = np.asarray(seg_ids, np.int64)
        if not len(idx):
            L = (np.asarray(table).shape[1] - 1) // 3
            return np.zeros((0, gather_width(L)), np.float32)
        self.op_dispatches += 1
        self.host_syncs += 1
        return np.asarray(gather_stats(
            jnp.asarray(np.asarray(table, np.float32)), idx))

    def prefix_fold(self, table):
        # same structural-op argument as fold_segments_scan
        return JaxBackend.prefix_fold(self, table)


__all__ = [
    "ComputeBackend", "FactBlock", "NumpyBackend", "JaxBackend",
    "PallasBackend", "register_backend", "get_backend",
    "available_backends", "resolve_backend_name", "DEFAULT_BACKEND",
    "ENV_VAR", "KPI_LANES", "FOLD_BLOCK", "fold_width", "gather_width",
    "empty_fold_state", "combine_fold", "bitrev_permutation",
    "prefix_fold_reference",
]
