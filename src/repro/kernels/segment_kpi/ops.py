"""Public wrappers: pad to the kernel block size, combine the per-block
partials ON DEVICE (no host sync — the results stay jax arrays, so the
pallas backend's ``FactBlock``s remain device-resident until the
warehouse-load boundary).

``segment_kpi`` is the pallas backend's ``transform_and_rollup`` core:
one fused kernel emits the fact rows AND the per-unit KPI aggregate, so
the hot path never re-uploads the block for a separate rollup dispatch.
``fold_segments`` receives the serving layer's SEGMENT-COMPACTED deltas
(``n_segments`` here is the compacted tree width, not the view's full
segment count — see ``repro.core.backend._fold_blocks``)."""
from __future__ import annotations

import jax.numpy as jnp

from repro.core.device import pallas_interpret
from repro.kernels.segment_kpi.segment_kpi import (fold_segments_kernel,
                                                   gather_stats_kernel,
                                                   segment_kpi_kernel,
                                                   segment_rollup_kernel)


def segment_kpi(prod, eq_rows, q_rows, *, n_units: int = 32,
                block: int = 256):
    """Fused fact build + per-unit KPI rollup: returns (facts [N, 10],
    agg [n_units, 5]), both device-resident (agg's cross-block sum is a
    device op). Rows whose joined master rows are marked missing
    (col 1 < 0) — and pad rows, whose unit id is -1 — contribute nothing
    to the aggregate."""
    n = prod.shape[0]
    pad = (-n) % block
    if pad:
        padrow = jnp.full((pad, 8), -1.0, jnp.float32)
        prod = jnp.concatenate([prod, padrow])
        eq_rows = jnp.concatenate([eq_rows, padrow])
        q_rows = jnp.concatenate([q_rows, padrow])
    facts, agg = segment_kpi_kernel(prod, eq_rows, q_rows, n_units=n_units,
                                    block=block, interpret=pallas_interpret())
    return facts[:n], agg.sum(axis=0)


def segment_rollup(facts, *, n_units: int = 32, block: int = 256):
    """Per-unit KPI rollup of fact rows [N, 10]; pads with invalid rows."""
    n = facts.shape[0]
    pad = (-n) % block
    if pad:
        facts = jnp.concatenate(
            [facts, jnp.zeros((pad, facts.shape[1]), jnp.float32)])
    agg = segment_rollup_kernel(facts, n_units=n_units, block=block,
                                interpret=pallas_interpret())
    return agg.sum(axis=0)


def fold_segments(packed, *, n_segments: int = 32, block: int = 256):
    """Serving-layer delta fold of packed rows [N, 1+L] (seg id + value
    lanes): count/sum/min/max per segment, one fused kernel dispatch.
    Pads with seg = -1 identity rows; combines the per-block tables."""
    n, w = packed.shape
    L = w - 1
    pad = (-n) % block
    if pad:
        padrow = jnp.concatenate(
            [jnp.full((pad, 1), -1.0, jnp.float32),
             jnp.zeros((pad, L), jnp.float32)], axis=1)
        packed = jnp.concatenate([packed, padrow])
    agg = fold_segments_kernel(packed, n_segments=n_segments, block=block,
                               interpret=pallas_interpret())  # [nb, S, 1+3L]
    return jnp.concatenate(
        [agg[:, :, :1 + L].sum(axis=0),
         agg[:, :, 1 + L:1 + 2 * L].min(axis=0),
         agg[:, :, 1 + 2 * L:].max(axis=0)], axis=-1)


def gather_stats(table, idx, *, block: int = 256):
    """Batched point-query gather against a packed [S, 1+3L] fold table:
    returns [len(idx), 1+4L] ([count | sums | mins | maxs | means]) in one
    kernel dispatch. ``idx`` int segment ids in [0, S); pads the batch to
    a block multiple with id 0 (valid row, sliced off after)."""
    idx = jnp.asarray(idx, jnp.float32)[:, None]          # [N, 1]
    n = idx.shape[0]
    pad = (-n) % block
    if pad:
        idx = jnp.concatenate([idx, jnp.zeros((pad, 1), jnp.float32)])
    out = gather_stats_kernel(table, idx, block=block,
                              interpret=pallas_interpret())
    return out[:n]


__all__ = ["fold_segments", "fold_segments_kernel", "gather_stats",
           "gather_stats_kernel", "segment_kpi", "segment_kpi_kernel",
           "segment_rollup", "segment_rollup_kernel"]
