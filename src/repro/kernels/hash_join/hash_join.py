"""Streaming hash-join probe kernel (TPU Pallas) — the In-memory Table
Updater / Data Transformer join of DOD-ETL.

Linear probing over the worker's open-addressing master cache, blocked so
the table never has to fit VMEM: grid (query_blocks, slot_tiles), the slot
axis sequential. Each step compares a [block_q, 1] column of query keys
with a [1, SLOT_TILE] row of table keys. For a query hashed to slot ``h``
the probe distance of slot ``j`` is ``(j - h) mod n_slots``; the chain
stops at the nearest slot within ``MAX_PROBES`` that holds the key (hit)
or is empty (miss). Each query keeps, in its output block, the smallest
stop code seen so far, so a chain that crosses a tile edge — or wraps from
the last slot to slot 0 — resolves exactly as the sequential probe of
``repro.core.cache.lookup_ref`` does.

Only the key lane enters the kernel. The winning slots' values and txn
times are gathered once afterwards (as ``lookup_ref`` does), so the gather
is exact for every payload bit pattern and needs no matmul.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.cache import MAX_PROBES, hash32_jnp

SLOT_TILE = 512
# stop code = 2 * probe distance + (0 for a hit, 1 for an empty slot); odd,
# so "no stop within the probe budget" reads as a miss
_NO_STOP = 2 * MAX_PROBES + 1


def _probe_kernel(q_ref, h_ref, keys_ref, code_ref, *, n_slots: int,
                  slot_tile: int):
    t = pl.program_id(1)

    @pl.when(t == 0)
    def _init():
        code_ref[...] = jnp.full(code_ref.shape, _NO_STOP, jnp.int32)

    q = q_ref[...]                                        # [Bq, 1]
    h = h_ref[...]                                        # [Bq, 1]
    keys = keys_ref[...]                                  # [1, T]
    slot = t * slot_tile + jax.lax.broadcasted_iota(jnp.int32, keys.shape, 1)
    dist = slot - h
    dist = jnp.where(dist < 0, dist + n_slots, dist)      # [Bq, T]
    hit = keys == q
    stop = (dist < MAX_PROBES) & (hit | (keys == -1))
    code = jnp.where(stop, 2 * dist + jnp.where(hit, 0, 1), _NO_STOP)
    code_ref[...] = jnp.minimum(code_ref[...],
                                jnp.min(code, axis=1, keepdims=True))


@functools.partial(jax.jit, static_argnames=("block_q", "interpret"))
def hash_join_kernel(query_keys: jax.Array, keys_tbl: jax.Array,
                     vals_tbl: jax.Array, txn_tbl: jax.Array, *,
                     block_q: int = 256, interpret: bool):
    """query_keys: [N] i32 (N % block_q == 0); keys_tbl: [S] i32 (-1 =
    empty); vals_tbl: [S, W] f32; txn_tbl: [S] int. Returns (vals [N, W]
    f32, found [N] bool, txn [N] i32), equal to ``lookup_ref``."""
    n = query_keys.shape[0]
    n_slots = keys_tbl.shape[0]
    assert n % block_q == 0, (n, block_q)
    q = query_keys.astype(jnp.int32)
    h = (hash32_jnp(q) % jnp.uint32(n_slots)).astype(jnp.int32)
    tile = min(SLOT_TILE, n_slots)
    keys = keys_tbl.astype(jnp.int32)
    pad = (-n_slots) % tile
    if pad:                       # -2 never matches a query and is never empty
        keys = jnp.concatenate([keys, jnp.full((pad,), -2, jnp.int32)])

    kernel = functools.partial(_probe_kernel, n_slots=n_slots,
                               slot_tile=tile)
    code = pl.pallas_call(
        kernel,
        grid=(n // block_q, (n_slots + pad) // tile),
        in_specs=[
            pl.BlockSpec((block_q, 1), lambda i, t: (i, 0)),
            pl.BlockSpec((block_q, 1), lambda i, t: (i, 0)),
            pl.BlockSpec((1, tile), lambda i, t: (0, t)),
        ],
        out_specs=pl.BlockSpec((block_q, 1), lambda i, t: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, 1), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(q[:, None], h[:, None], keys[None, :])[:, 0]

    found = code % 2 == 0
    slot = h + code // 2
    slot = jnp.where(found, jnp.where(slot >= n_slots, slot - n_slots, slot),
                     0)
    vals = jnp.where(found[:, None], vals_tbl[slot], 0)
    txn = jnp.where(found, txn_tbl[slot].astype(jnp.int32), 0)
    return vals, found, txn
