"""Public wrapper used by the ``pallas`` compute backend
(repro.core.backend.PallasBackend)."""
from __future__ import annotations

import jax.numpy as jnp

from repro.core.device import pallas_interpret
from repro.kernels.hash_join.hash_join import hash_join_kernel


def hash_join(query_keys, keys_tbl, vals_tbl, txn_tbl, block_q: int = 256):
    n = query_keys.shape[0]
    pad = (-n) % block_q
    if pad:
        query_keys = jnp.concatenate(
            [query_keys, jnp.full((pad,), -2, query_keys.dtype)])
    vals, found, txn = hash_join_kernel(
        query_keys, keys_tbl, vals_tbl, txn_tbl, block_q=block_q,
        interpret=pallas_interpret())
    if pad:
        vals, found, txn = vals[:n], found[:n], txn[:n]
    return vals, found, txn


__all__ = ["hash_join", "hash_join_kernel"]
