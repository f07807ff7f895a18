"""Fact-grain splitting + OEE KPI kernel (TPU Pallas) — the Data
Transformer's numeric core (paper Fig. 3 + §4 KPIs), fused:

  per record: interval intersection (production window x equipment status),
  availability / performance / quality / OEE, fact packing — then a
  per-equipment segmented reduction (sum of KPIs + counts) via one-hot
  matmul, so the OLAP rollup leaves the kernel already aggregated.

Grid: (record_blocks,) parallel; the per-unit accumulator is a second
output reduced across blocks by the caller (associative sum).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

EPS = 1e-6
N_FACT = 10
# one-hot matmuls select or sum f32 values. At default precision the TPU
# may multiply f32 operands in reduced (bf16) passes, so every dot asks for
# full f32 precision: a one-hot selection of finite values is then exact
_EXACT = jax.lax.Precision.HIGHEST


def _kpi_kernel(prod_ref, eq_ref, q_ref, facts_ref, agg_ref, *,
                n_units: int, block: int):
    prod = prod_ref[...]                                  # [B, 8]
    eq = eq_ref[...]                                      # [B, 8] joined rows
    qrow = q_ref[...]                                     # [B, 8]

    t_start, t_end = prod[:, 3], prod[:, 4]
    qty, speed = prod[:, 5], prod[:, 6]
    e_start, e_end = eq[:, 3], eq[:, 4]
    status, max_speed, planned = eq[:, 5], eq[:, 6], eq[:, 7]
    defects, scrap = qrow[:, 4], qrow[:, 6]

    inter_lo = jnp.maximum(t_start, e_start)
    inter_hi = jnp.minimum(t_end, e_end)
    overlap = jnp.maximum(inter_hi - inter_lo, 0.0)
    duration = jnp.maximum(t_end - t_start, EPS)
    seg_on = jnp.where(status > 0.5, overlap, 0.0)
    seg_off = duration - seg_on

    availability = jnp.clip(seg_on / jnp.maximum(planned, EPS), 0.0, 1.0)
    performance = jnp.clip(qty / jnp.maximum(max_speed * duration, EPS),
                           0.0, 1.0)
    good = jnp.maximum(qty - defects - scrap, 0.0)
    quality = jnp.clip(good / jnp.maximum(qty, EPS), 0.0, 1.0)
    oee = availability * performance * quality

    valid = (eq[:, 1] >= 0) & (qrow[:, 1] >= 0)
    facts = jnp.stack([prod[:, 1], t_start, t_end, availability,
                       performance, quality, oee, seg_on, seg_off,
                       valid.astype(jnp.float32)], axis=-1)
    facts_ref[...] = facts

    # segmented rollup: one-hot(equipment) @ [kpis, 1] on the MXU
    unit = prod[:, 1].astype(jnp.int32)
    iota = jax.lax.broadcasted_iota(jnp.int32, (block, n_units), 1)
    onehot = ((iota == unit[:, None]) & valid[:, None]).astype(jnp.float32)
    kpis = jnp.stack([availability, performance, quality, oee,
                      jnp.ones_like(oee)], axis=-1)      # [B, 5]
    agg_ref[0] = jax.lax.dot_general(
        onehot, kpis, (((0,), (0,)), ((), ())), precision=_EXACT,
        preferred_element_type=jnp.float32)              # [n_units, 5]


def _rollup_kernel(facts_ref, agg_ref, *, n_units: int, block: int):
    facts = facts_ref[...]                                # [B, N_FACT]
    unit = facts[:, 0].astype(jnp.int32)
    valid = facts[:, 9] > 0.5
    iota = jax.lax.broadcasted_iota(jnp.int32, (block, n_units), 1)
    onehot = ((iota == unit[:, None]) & valid[:, None]).astype(jnp.float32)
    kpis = jnp.concatenate(
        [facts[:, 3:7], jnp.ones((block, 1), jnp.float32)], axis=-1)
    agg_ref[0] = jax.lax.dot_general(
        onehot, kpis, (((0,), (0,)), ((), ())), precision=_EXACT,
        preferred_element_type=jnp.float32)               # [n_units, 5]


def _fold_kernel(packed_ref, out_ref, *, n_segments: int, n_lanes: int,
                 block: int):
    """Serving-layer delta fold: per segment, count + sum + min + max of
    every value lane, fused in one pass over the block.

    ``packed`` rows are [seg_id | lane_0 .. lane_{L-1}] f32 (seg as f32 —
    exact below 2^24; a negative seg marks a padding row that contributes
    the identity). count + sums ride the MXU as one one-hot matmul against
    [1 | lanes]; min/max are masked VPU reductions per lane.
    """
    packed = packed_ref[...]                              # [B, 1+L]
    seg = packed[:, 0].astype(jnp.int32)
    vals = packed[:, 1:]                                  # [B, L]

    iota = jax.lax.broadcasted_iota(jnp.int32, (block, n_segments), 1)
    hit = iota == seg[:, None]                            # [B, S] bool
    onehot = hit.astype(jnp.float32)
    ones = jnp.ones((block, 1), jnp.float32)
    cnt_sums = jax.lax.dot_general(                       # [S, 1+L]
        onehot, jnp.concatenate([ones, vals], axis=-1),
        (((0,), (0,)), ((), ())), precision=_EXACT,
        preferred_element_type=jnp.float32)

    mins = []
    maxs = []
    for j in range(n_lanes):                              # static lane loop
        lane = jnp.broadcast_to(vals[:, j:j + 1], (block, n_segments))
        mins.append(jnp.min(jnp.where(hit, lane, jnp.inf), axis=0))
        maxs.append(jnp.max(jnp.where(hit, lane, -jnp.inf), axis=0))
    out_ref[0] = jnp.concatenate(
        [cnt_sums, jnp.stack(mins, axis=-1), jnp.stack(maxs, axis=-1)],
        axis=-1)                                          # [S, 1+3L]


def _gather_kernel(table_ref, idx_ref, out_ref, *, n_segments: int,
                   n_lanes: int, block: int):
    """Batched read-path gather: for each query row, pick one segment's
    packed fold stats out of the full [S, 1+3L] view table and derive the
    lane means — the whole batch in one kernel pass.

    count + sums ride the MXU as a one-hot matmul (exact: each one-hot row
    selects a single finite table row); min/max lanes use masked VPU
    reductions instead, because the table's empty-segment identities are
    ±inf and ``0 * inf`` would poison a matmul gather with NaNs."""
    table = table_ref[...]                                # [S, 1+3L]
    idx = idx_ref[...][:, 0].astype(jnp.int32)            # [B]
    L = n_lanes

    iota = jax.lax.broadcasted_iota(jnp.int32, (block, n_segments), 1)
    hit = iota == idx[:, None]                            # [B, S] bool
    onehot = hit.astype(jnp.float32)
    cnt_sums = jax.lax.dot_general(                       # [B, 1+L]
        onehot, table[:, :1 + L],
        (((1,), (0,)), ((), ())), precision=_EXACT,
        preferred_element_type=jnp.float32)

    mins = []
    maxs = []
    for j in range(L):                                    # static lane loop
        mincol = jnp.broadcast_to(table[:, 1 + L + j][None, :],
                                  (block, n_segments))
        maxcol = jnp.broadcast_to(table[:, 1 + 2 * L + j][None, :],
                                  (block, n_segments))
        mins.append(jnp.min(jnp.where(hit, mincol, jnp.inf), axis=1))
        maxs.append(jnp.max(jnp.where(hit, maxcol, -jnp.inf), axis=1))

    cnt = cnt_sums[:, :1]
    means = jnp.where(cnt > 0, cnt_sums[:, 1:] / cnt, jnp.nan)
    out_ref[...] = jnp.concatenate(
        [cnt_sums, jnp.stack(mins, axis=-1), jnp.stack(maxs, axis=-1),
         means], axis=-1)                                 # [B, 1+4L]


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def gather_stats_kernel(table: jax.Array, idx: jax.Array, *,
                        block: int = 256, interpret: bool):
    """table [S, 1+3L] packed fold stats; idx [N, 1] f32 segment ids
    (exact below 2^24), N % block == 0, every id in [0, S). Returns
    [N, 1+4L]: [count | sums | mins | maxs | means] per query row."""
    n = idx.shape[0]
    s, w = table.shape
    n_lanes = (w - 1) // 3
    assert n % block == 0
    nb = n // block
    width = 1 + 4 * n_lanes
    kernel = functools.partial(_gather_kernel, n_segments=s,
                               n_lanes=n_lanes, block=block)
    return pl.pallas_call(
        kernel,
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((s, w), lambda i: (0, 0)),       # full table
            pl.BlockSpec((block, 1), lambda i: (i, 0)),
        ],
        out_specs=[pl.BlockSpec((block, width), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((n, width), jnp.float32)],
        interpret=interpret,
    )(table, idx)[0]


@functools.partial(jax.jit,
                   static_argnames=("n_segments", "block", "interpret"))
def fold_segments_kernel(packed: jax.Array, *, n_segments: int = 32,
                         block: int = 256, interpret: bool):
    """packed [N, 1+L] f32 (seg id lane + L value lanes), N % block == 0.
    Returns [blocks, n_segments, 1+3L]: per-block packed fold tables —
    caller combines across blocks (count/sum add, min min, max max)."""
    n, w = packed.shape
    n_lanes = w - 1
    assert n % block == 0
    nb = n // block
    width = 1 + 3 * n_lanes
    kernel = functools.partial(_fold_kernel, n_segments=n_segments,
                               n_lanes=n_lanes, block=block)
    return pl.pallas_call(
        kernel,
        grid=(nb,),
        in_specs=[pl.BlockSpec((block, w), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((1, n_segments, width),
                                lambda i: (i, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct((nb, n_segments, width),
                                        jnp.float32)],
        interpret=interpret,
    )(packed)[0]


@functools.partial(jax.jit, static_argnames=("n_units", "block", "interpret"))
def segment_rollup_kernel(facts: jax.Array, *, n_units: int = 32,
                          block: int = 256, interpret: bool):
    """Standalone per-unit KPI rollup over already-built fact rows
    [N, N_FACT] f32 (col 0 = unit, col 9 = valid flag): one-hot matmul on
    the MXU, same discipline as the fused ``segment_kpi_kernel`` epilogue.
    Returns agg [blocks, n_units, 5] — caller sums over blocks."""
    n = facts.shape[0]
    assert n % block == 0
    nb = n // block
    kernel = functools.partial(_rollup_kernel, n_units=n_units, block=block)
    return pl.pallas_call(
        kernel,
        grid=(nb,),
        in_specs=[pl.BlockSpec((block, N_FACT), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((1, n_units, 5), lambda i: (i, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct((nb, n_units, 5), jnp.float32)],
        interpret=interpret,
    )(facts)[0]


@functools.partial(jax.jit, static_argnames=("n_units", "block", "interpret"))
def segment_kpi_kernel(prod: jax.Array, eq_rows: jax.Array,
                       q_rows: jax.Array, *, n_units: int = 32,
                       block: int = 256, interpret: bool):
    """prod/eq_rows/q_rows: [N, 8] f32 (production payloads + joined master
    rows; a row with col1 < 0 marks a join miss). Returns (facts [N, 10],
    agg [blocks, n_units, 5]) — caller sums agg over blocks."""
    n = prod.shape[0]
    assert n % block == 0
    nb = n // block
    kernel = functools.partial(_kpi_kernel, n_units=n_units, block=block)
    return pl.pallas_call(
        kernel,
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((block, 8), lambda i: (i, 0)),
            pl.BlockSpec((block, 8), lambda i: (i, 0)),
            pl.BlockSpec((block, 8), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block, N_FACT), lambda i: (i, 0)),
            pl.BlockSpec((1, n_units, 5), lambda i: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, N_FACT), jnp.float32),
            jax.ShapeDtypeStruct((nb, n_units, 5), jnp.float32),
        ],
        interpret=interpret,
    )(prod, eq_rows, q_rows)
