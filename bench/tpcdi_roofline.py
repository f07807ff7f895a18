"""Bytes of the DimTrade transform, whose roofline share the benchmark
reports, counted from shapes.

``dimtrade_transform_kernel`` (``repro.core.dimtrade``) takes a block of B
Trade CDC rows [B, 16] int32 (padded to a power of two) and probes two
caches: DimAccount point-in-time and DimSecurity by symbol. The least
traffic one call must make for its B real records (padding rows are not
work):

* read the block: B * 16 * 4 bytes;
* DimAccount: a point-in-time probe cannot stop at its key's first
  version, so it reads at least two slots' key and EffectiveDate lanes
  (the version and the slot that ends the key's chain), then gathers the
  matched 8-lane int32 row: 2 * (4 + 4) + 32 bytes;
* DimSecurity: at least one 4-byte slot key and the matched 8-lane row:
  4 + 32 bytes;
* write the rows [B, 21] int32 and the found mask [B] bool.

Its arithmetic (a few dozen integer operations per record, the calendar
among them) is far below the chip's peak over this byte count, so
bandwidth bounds it.
"""
from __future__ import annotations

from typing import Optional

PROGRAM = "jit_dimtrade_transform_kernel"
IN_BYTES = 16 * 4
ACCOUNT_BYTES = 2 * (4 + 4) + 8 * 4
SECURITY_BYTES = 4 + 8 * 4
OUT_BYTES = 21 * 4 + 1


def dimtrade_bytes(b: int) -> int:
    return b * (IN_BYTES + ACCOUNT_BYTES + SECURITY_BYTES + OUT_BYTES)


def dimtrade_share(run) -> Optional[float]:
    """Percent of the roofline the DimTrade program reached in the traced
    window; None when the trace holds no execution of it."""
    dt = run.device_trace
    if not dt or not run.peaks:
        return None
    secs = dt["modules"].get(PROGRAM, 0.0)
    lo, hi = run.window
    calls = [n for t, n in run.transform_calls if lo <= t < hi]
    if secs <= 0 or not calls:
        return None
    total = sum(dimtrade_bytes(n) for n in calls)
    return 100.0 * (total / run.peaks["hbm_bytes_s"]) / secs
