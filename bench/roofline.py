"""Operations and bytes of the programs whose roofline share the benchmark
reports, counted from shapes.

``transform_rollup_kernel`` (``repro.core.transformer``) takes a block
of B production payloads [B, 8] f32 (padded to a power of two) and probes
two caches: the equipment cache by unit and the quality cache by
``prod_id``, plus ``join_depth - 1`` hops into the equipment cache. The
least traffic one call must make for its B real records (padding rows are
not work: a change that pads less must not read as a lower share):

* read the block: B * 8 * 4 bytes;
* per probe and record, read at least one 4-byte slot key (a hit on the
  first slot) and gather the matched 8-lane f32 row: (4 + 32) bytes, for
  ``join_depth + 1`` probes (the hops' rows feed a sum, so they are read);
* write the facts [B, 10] f32, the found mask [B] bool and the per-unit
  rollup [n_units, 5] f32.

Its arithmetic (a few dozen flops per record) is far below the chip's
bf16 peak over this byte count, so bandwidth bounds it.
"""
from __future__ import annotations

from typing import Optional

PROGRAM = "jit_transform_rollup_kernel"
ROW_BYTES = 8 * 4
KEY_BYTES = 4
FACT_BYTES = 10 * 4
ROLLUP_LANES = 5


def transform_bytes(b: int, join_depth: int, n_units: int) -> int:
    probes = join_depth + 1
    return (b * ROW_BYTES + probes * b * (KEY_BYTES + ROW_BYTES)
            + b * FACT_BYTES + b + n_units * ROLLUP_LANES * 4)


def transform_share(run) -> Optional[float]:
    """Percent of the roofline the transform program reached in the traced
    window; None when the trace holds no execution of it."""
    dt = run.device_trace
    if not dt or not run.peaks:
        return None
    secs = dt["modules"].get(PROGRAM, 0.0)
    lo, hi = run.window
    calls = [n for t, n in run.transform_calls if lo <= t < hi]
    if secs <= 0 or not calls:
        return None
    c = run.config
    total = sum(transform_bytes(n, c["join_depth"], c["n_units"])
                for n in calls)
    return 100.0 * (total / run.peaks["hbm_bytes_s"]) / secs
