"""Data Transformer (paper §3.1.2): per-partition streaming join of
operational records against the In-memory cache, fact-grain splitting
(Fig. 3: intersect production windows with equipment-status intervals) and
OEE KPI computation (§4: availability / performance / quality / OEE).

The numeric core is ONE fused dispatch over fixed-width arrays, routed
through the pluggable compute-backend layer (``repro.core.backend``):
``numpy`` reference, ``jax`` jitted (``transform_kernel`` below), or the
``hash_join`` + ``segment_kpi`` Pallas kernels on TPU.

Payload layouts (see configs.dod_etl.steelworks_config):
  production : (prod_id, equipment_id, txn_time, t_start, t_end, qty, speed, order_id)
  equipment  : (row_id, equipment_id, txn_time, t_start, t_end, status, max_speed, planned)
  quality    : (row_id, equipment_id, txn_time, prod_id, defects, grade, scrap, rework)
"""
from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

EPS = 1e-6

FACT_COLUMNS = ("equipment_id", "t_start", "t_end", "availability",
                "performance", "quality", "oee", "seg_on", "seg_off", "valid")


def _transform_math(prod: jax.Array,
                    eq_keys: jax.Array, eq_vals: jax.Array, eq_txn: jax.Array,
                    q_keys: jax.Array, q_vals: jax.Array, q_txn: jax.Array,
                    join_depth: int = 1) -> Tuple[jax.Array, jax.Array]:
    """Traced body shared by ``transform_kernel`` and
    ``transform_rollup_kernel`` — identical math, so fusing the rollup
    into the dispatch can never change the facts."""
    from repro.core.cache import lookup_ref

    equip_id = prod[:, 1].astype(jnp.int32)
    prod_id = prod[:, 0].astype(jnp.int32)

    eq_rows, eq_found, _ = lookup_ref(equip_id, eq_keys, eq_vals, eq_txn)
    q_rows, q_found, _ = lookup_ref(prod_id, q_keys, q_vals, q_txn)
    # normalized-model join chains (§4.1.4 complexity knob): every extra
    # hop re-probes the cache. The hop keys are independent of the probed
    # values, so all hops run as ONE flattened probe over [(jd-1)*n] keys —
    # identical probe count and results, but a single wide dispatch instead
    # of jd-1 narrow ones (narrow sequential probes thrash when worker
    # threads dispatch concurrently)
    if join_depth > 1:
        mod = jnp.int32(max(eq_keys.shape[0] // 4, 1))
        hop_keys = ((equip_id[None, :]
                     + jnp.arange(1, join_depth, dtype=jnp.int32)[:, None])
                    % mod)
        extra, _, _ = lookup_ref(hop_keys.reshape(-1),
                                 eq_keys, eq_vals, eq_txn)
        # 0 * sum(hops) == sum of the per-hop 0-weighted adds
        eq_rows = eq_rows + 0.0 * extra.reshape(
            join_depth - 1, equip_id.shape[0], -1).sum(axis=0)
    found = eq_found & q_found

    t_start, t_end = prod[:, 3], prod[:, 4]
    qty, speed = prod[:, 5], prod[:, 6]
    e_start, e_end = eq_rows[:, 3], eq_rows[:, 4]
    status = eq_rows[:, 5]
    max_speed = eq_rows[:, 6]
    planned = eq_rows[:, 7]
    defects, scrap = q_vals_cols(q_rows)

    # ---- fact-grain split (Fig. 3): production window vs status interval
    inter_lo = jnp.maximum(t_start, e_start)
    inter_hi = jnp.minimum(t_end, e_end)
    overlap = jnp.maximum(inter_hi - inter_lo, 0.0)
    duration = jnp.maximum(t_end - t_start, EPS)
    seg_on = jnp.where(status > 0.5, overlap, 0.0)
    seg_off = duration - seg_on

    # ---- OEE (TPM indicators, §4)
    availability = jnp.clip(seg_on / jnp.maximum(planned, EPS), 0.0, 1.0)
    performance = jnp.clip(qty / jnp.maximum(max_speed * duration, EPS),
                           0.0, 1.0)
    good = jnp.maximum(qty - defects - scrap, 0.0)
    quality = jnp.clip(good / jnp.maximum(qty, EPS), 0.0, 1.0)
    oee = availability * performance * quality

    facts = jnp.stack([
        prod[:, 1], t_start, t_end, availability, performance, quality, oee,
        seg_on, seg_off, found.astype(jnp.float32)], axis=-1)
    return facts, found


@functools.partial(jax.jit, static_argnames=("join_depth",))
def transform_kernel(prod: jax.Array,
                     eq_keys: jax.Array, eq_vals: jax.Array, eq_txn: jax.Array,
                     q_keys: jax.Array, q_vals: jax.Array, q_txn: jax.Array,
                     join_depth: int = 1) -> Tuple[jax.Array, jax.Array]:
    """prod: [n, 8] f32 production payloads. Returns (facts [n, 10] f32,
    found [n] bool). ``join_depth > 1`` replays the join chain to model
    normalized (ISA-95-style) schemas — §4.1.4's complexity knob."""
    return _transform_math(prod, eq_keys, eq_vals, eq_txn,
                           q_keys, q_vals, q_txn, join_depth)


@functools.partial(jax.jit, static_argnames=("join_depth", "n_units"))
def transform_rollup_kernel(prod: jax.Array,
                            eq_keys: jax.Array, eq_vals: jax.Array,
                            eq_txn: jax.Array,
                            q_keys: jax.Array, q_vals: jax.Array,
                            q_txn: jax.Array,
                            join_depth: int = 1, n_units: int = 1
                            ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """The device-resident hot path's SINGLE dispatch: transform + per-unit
    KPI rollup fused. Returns (facts [n, 10] f32, found [n] bool,
    agg [n_units, 5] f32) where agg matches ``segment_reduce`` over the
    block's valid facts (pad rows carry unit -1 and drop out of the
    in-range guard, exactly like out-of-range units).

    Nothing is donated: no output has the production block's [n, 8]
    shape, so XLA could never reuse its buffer."""
    facts, found = _transform_math(prod, eq_keys, eq_vals, eq_txn,
                                   q_keys, q_vals, q_txn, join_depth)
    unit = facts[:, 0].astype(jnp.int32)
    ok = found & (unit >= 0) & (unit < n_units)
    kpis = jnp.concatenate(
        [facts[:, 3:7], jnp.ones((facts.shape[0], 1), jnp.float32)],
        axis=-1)
    kpis = jnp.where(ok[:, None], kpis, 0.0)
    # rows failing the guard route to a trash segment past n_units
    agg = jax.ops.segment_sum(kpis, jnp.where(ok, unit, n_units),
                              num_segments=n_units + 1)[:n_units]
    return facts, found, agg


def q_vals_cols(q_rows: jax.Array) -> Tuple[jax.Array, jax.Array]:
    return q_rows[:, 4], q_rows[:, 6]


class DataTransformer:
    """Stateful wrapper: caches + late buffer + metrics for one worker.
    The numeric core is delegated to the selected ``ComputeBackend`` —
    one fused transform dispatch per call, regardless of how many queue
    partitions were coalesced into the batch — whose body the
    configuration's ``schema`` picks. ``caches`` are the worker's master
    caches in the schema's order (steelworks: equipment, quality). With
    ``n_units`` set the steelworks dispatch also carries the per-unit KPI
    rollup (the fused ``transform_and_rollup`` op), and the result stays
    device-resident as a ``FactBlock`` until the warehouse-load
    boundary."""

    def __init__(self, caches, buffer, join_depth: int = 1, backend=None,
                 n_units: Optional[int] = None, schema: str = "steelworks"):
        from repro.core.backend import get_backend
        self.caches = tuple(caches)
        self.buffer = buffer
        self.join_depth = join_depth
        self.n_units = n_units    # fused-rollup width (None: facts only)
        self.schema = schema
        self.backend = get_backend(backend)
        self.records_out = 0
        self.records_late = 0
        self.dispatches = 0     # device dispatch count (the tentpole metric)

    def watermark(self) -> int:
        return min(c.watermark for c in self.caches)

    def transform_block(self, batch, *snapshots):
        """Pure numeric transform of a RecordBatch: ONE backend dispatch,
        no buffer interaction, NO host sync — returns a device-resident
        ``FactBlock`` (rows + found, and for steelworks the fused per-unit
        rollup when ``n_units`` is configured). The concurrent runtime's
        transform stage calls this with immutable ``CacheSnapshot`` views
        of every cache (taken under the worker's cache lock) so the
        dispatch itself runs LOCK-FREE and overlaps the ingest stage's
        master pumps; the block materializes to host only in the load
        stage, under the worker's commit lock, so device compute + D2H
        overlap the load stage's host work instead of blocking here."""
        block = self.backend.transform_block(
            batch.payload, *(snapshots or self.caches), schema=self.schema,
            join_depth=self.join_depth, n_units=self.n_units)
        self.dispatches += 1
        return block

    def process_block(self, prod_batch):
        """Retry-merge + dispatch WITHOUT the host sync: pops
        watermark-ready buffered records, concats them ahead of the new
        batch, issues one dispatch. Returns (block, merged_batch) —
        block is None when there was nothing to transform. ``finish``
        (or the load stage) completes the late-buffer accounting once the
        block is materialized."""
        from repro.core.records import RecordBatch

        retry = self.buffer.pop_ready(self.watermark())
        batch = RecordBatch.concat([retry, prod_batch])
        if not len(batch):
            return None, batch
        return self.transform_block(batch), batch

    def finish(self, block, batch) -> Tuple[np.ndarray, int]:
        """Host-side epilogue of ``process_block``: materialize the block
        (the step's one sync), buffer the late records, account metrics.
        Returns (good_facts [m, 10], n_late)."""
        facts, found = block.to_host()
        late = batch.filter(~found)
        self.buffer.push(late)
        self.records_late += len(late)
        good_facts = facts[found]
        self.records_out += len(good_facts)
        return good_facts, len(late)

    def process(self, prod_batch) -> Tuple[np.ndarray, int]:
        """prod_batch: RecordBatch of production records. Returns
        (facts [m, 10], n_late). Late records (missing master data) go to
        the Operational Message Buffer; buffered records whose txn_time
        passed the cache watermark are retried first (paper §3.1.2).

        Backends pad to power-of-two buckets internally so jitted kernels
        compile once per bucket, not once per arrival size (a 100x
        throughput cliff otherwise)."""
        block, batch = self.process_block(prod_batch)
        if block is None:
            return np.zeros((0, len(FACT_COLUMNS)), np.float32), 0
        return self.finish(block, batch)
