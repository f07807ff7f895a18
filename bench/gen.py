"""Traffic for the steelworks deployments, made from ``--seed``.

The benchmark's own copy of the data logic of ``repro.data.sampler``: a
production record per product, one quality inspection keyed by its
``prod_id`` and one equipment-status row keyed by its unit, the paper's
late-master tail (§3.2), and the sampler's time axis (one production window
every ``TICK`` time units; 8 where the sampler has 10, so that times stay
exact in float32 for a backlog of millions of records). Unlike the sampler it streams NEW ``prod_id``\\ s
for as long as the run lasts, so the quality cache grows with the stream as
it does in a plant, and every seed gets the same amount of work: the same
record count, the same number of records per unit and the same late count,
in another order.

The payload layouts are the system's input format (eight float32 lanes):

  production  prod_id, unit, txn, t_start, t_end, qty, speed, order_id
  equipment   row_id, unit, txn, t_start, t_end, status, max_speed, planned
  quality     row_id, unit, txn, prod_id, defects, grade, scrap, rework
"""
from __future__ import annotations

import dataclasses
import threading
import time
from typing import List, Optional

import numpy as np

TICK = 8                  # time units between consecutive production windows
T0 = 1_000                # time of record 0
QUALITY_ROW_BASE = 10_000_000
LATE_TXN_SHIFT = 1_000    # a late inspection's transaction time, as the sampler
EQ_START, EQ_END = 0.0, 1e9   # status intervals span the whole run
MAX_SPEED, PLANNED = 4.0, 60.0
# float32 holds every multiple of 8 below 2**27 and every integer below
# 2**24: t_start = T0 + TICK * i and prod_id stay exact, so a loaded fact
# names its record by (unit, t_start) and the probe finds its inspection
MAX_RECORDS = min(1 << 24, ((1 << 27) - T0) // TICK)


def rng(seed: int, stream: int) -> np.random.Generator:
    """Independent generator per stream of one seed; any integer seed."""
    s = abs(int(seed))
    return np.random.default_rng([s & 0xFFFFFFFF, s >> 32, int(seed < 0),
                                  stream])


@dataclasses.dataclass
class Records:
    """Every record of one run, indexed by ``prod_id``."""

    unit: np.ndarray          # i64 [n]
    t_start: np.ndarray       # i64 [n]
    t_end: np.ndarray         # i64 [n]
    txn: np.ndarray           # i64 [n]
    qty: np.ndarray           # f32 [n]
    speed: np.ndarray         # f32 [n]
    defects: np.ndarray       # f32 [n]
    grade: np.ndarray         # f32 [n]
    scrap: np.ndarray         # f32 [n]
    late: np.ndarray          # bool [n]: quality and status rows arrive late
    status: np.ndarray        # f32 [n_units]: each unit's status this run

    def __len__(self) -> int:
        return len(self.unit)

    def production(self, idx: np.ndarray) -> np.ndarray:
        """Production payloads [len(idx), 8] f32."""
        return np.stack([
            idx.astype(np.float32), self.unit[idx].astype(np.float32),
            self.txn[idx].astype(np.float32),
            self.t_start[idx].astype(np.float32),
            self.t_end[idx].astype(np.float32), self.qty[idx],
            self.speed[idx], idx.astype(np.float32)], axis=-1)


def make_records(n: int, n_units: int, late_frac: float, seed: int
                 ) -> Records:
    if n > MAX_RECORDS:
        raise ValueError(f"{n} records exceed the {MAX_RECORDS} whose "
                         f"t_start float32 holds exactly")
    r = rng(seed, 1)
    blocks = -(-n // n_units)
    # every block of n_units consecutive records covers each unit once
    unit = np.argsort(r.random((blocks, n_units)), axis=1).ravel()[:n]
    idx = np.arange(n, dtype=np.int64)
    t_start = T0 + idx * TICK
    t_end = t_start + r.integers(5, 50, n)
    late = np.zeros(n, bool)
    late[r.permutation(n)[:int(round(late_frac * n))]] = True
    return Records(
        unit=unit.astype(np.int64), t_start=t_start, t_end=t_end,
        txn=t_end + 1,
        qty=r.uniform(10, 100, n).astype(np.float32),
        speed=r.uniform(1, 5, n).astype(np.float32),
        defects=r.integers(0, 5, n).astype(np.float32),
        grade=r.integers(1, 4, n).astype(np.float32),
        scrap=r.integers(0, 3, n).astype(np.float32),
        late=late,
        status=(rng(seed, 2).random(n_units) > 0.2).astype(np.float32))


class Tables:
    """The deployment's table ids for the three source roles."""

    def __init__(self, names: List[str]):
        def first(part):
            return next(i for i, nm in enumerate(names) if part in nm)
        self.production = first("production")
        self.equipment = first("equipment")
        self.quality = first("quality")


def batches(rec: Records, idx: np.ndarray, tables: Tables, late: bool):
    """(equipment, quality, production) RecordBatches for records ``idx``;
    ``late`` marks master rows that arrive after their production record."""
    from repro.core.records import OP_INSERT, make_batch
    n = len(idx)
    unit = rec.unit[idx]
    shift = LATE_TXN_SHIFT if late else 0
    mtxn = rec.txn[idx] + shift
    eq = np.stack([
        idx.astype(np.float32), unit.astype(np.float32),
        mtxn.astype(np.float32), np.full(n, EQ_START, np.float32),
        np.full(n, EQ_END, np.float32), rec.status[unit],
        np.full(n, MAX_SPEED, np.float32), np.full(n, PLANNED, np.float32),
    ], axis=-1)
    qu = np.stack([
        (idx + QUALITY_ROW_BASE).astype(np.float32), unit.astype(np.float32),
        mtxn.astype(np.float32), idx.astype(np.float32), rec.defects[idx],
        rec.grade[idx], rec.scrap[idx], np.zeros(n, np.float32)], axis=-1)
    return (make_batch(tables.equipment, OP_INSERT, idx, unit, mtxn, eq),
            make_batch(tables.quality, OP_INSERT, idx + QUALITY_ROW_BASE,
                       unit, mtxn, qu),
            make_batch(tables.production, OP_INSERT, idx, unit,
                       rec.txn[idx], rec.production(idx)))


def history(first: int, n: int, n_units: int, status: np.ndarray,
            tables: Tables, seed: int):
    """The deployment's master data at the run's start: the quality
    inspections of ``n`` earlier products (``prod_id``\\ s ``first`` to
    ``first + n - 1``, which no record of the run references) and each
    unit's status row, all at transaction time 1, before the run's first
    transaction. Returns the (equipment, quality) RecordBatches."""
    from repro.core.records import OP_INSERT, make_batch
    if first + n > MAX_RECORDS:
        raise ValueError(f"prod_id {first + n} exceeds the {MAX_RECORDS} "
                         f"that float32 holds exactly")
    r = rng(seed, 5)
    blocks = -(-n // n_units)
    unit = np.argsort(r.random((blocks, n_units)), axis=1).ravel()[:n]
    pid = first + np.arange(n, dtype=np.int64)
    qu = np.stack([
        (pid + QUALITY_ROW_BASE).astype(np.float32), unit.astype(np.float32),
        np.ones(n, np.float32), pid.astype(np.float32),
        r.integers(0, 5, n).astype(np.float32),
        r.integers(1, 4, n).astype(np.float32),
        r.integers(0, 3, n).astype(np.float32), np.zeros(n, np.float32)],
        axis=-1)
    units = np.arange(n_units, dtype=np.int64)
    eq = np.stack([
        (first + units).astype(np.float32), units.astype(np.float32),
        np.ones(n_units, np.float32), np.full(n_units, EQ_START, np.float32),
        np.full(n_units, EQ_END, np.float32), status.astype(np.float32),
        np.full(n_units, MAX_SPEED, np.float32),
        np.full(n_units, PLANNED, np.float32)], axis=-1)
    return (make_batch(tables.equipment, OP_INSERT, first + units, units,
                       np.ones(n_units, np.int64), eq),
            make_batch(tables.quality, OP_INSERT, pid + QUALITY_ROW_BASE,
                       unit.astype(np.int64), np.ones(n, np.int64), qu))


# --------------------------------------------------------------- stream mode
class Stream:
    """Open-loop arrivals: every ``tick_ms`` one append to the change log
    carries the production records due in that tick, preceded by the
    master rows due then (the tick's on-time rows and the late rows whose
    delay has run out). The schedule does not slow when the system does:
    each append is due at ``t0 + k * tick``, and how late it was made is
    recorded."""

    def __init__(self, rec: Records, tables: Tables, rate: float,
                 tick_s: float, n_prod_ticks: int, late_delay_ticks,
                 seed: int):
        from repro.core.records import RecordBatch
        self.tick_s = tick_s
        cum = np.floor(rate * tick_s * np.arange(n_prod_ticks + 1)
                       ).astype(np.int64)
        n = int(cum[-1])
        if n > len(rec):
            raise ValueError("the stream needs more records than were made")
        tick_of = np.repeat(np.arange(n_prod_ticks), np.diff(cum))
        lo, hi = late_delay_ticks
        delay = rng(seed, 3).integers(lo, hi + 1, n)
        m_tick = np.where(rec.late[:n], tick_of + delay, tick_of)
        n_ticks = int(m_tick.max()) + 1 if n else n_prod_ticks
        m_order = np.lexsort((np.arange(n), m_tick))
        m_bounds = np.searchsorted(m_tick[m_order], np.arange(n_ticks + 1))
        self.prod_ticks = n_prod_ticks
        self.n_records = n
        self.batches = []
        for k in range(n_ticks):
            parts = []
            midx = m_order[m_bounds[k]:m_bounds[k + 1]]
            for late in (False, True):
                sel = midx[rec.late[midx] == late]
                if len(sel):
                    eq, qu, _ = batches(rec, sel, tables, late)
                    parts += [eq, qu]
            if k < n_prod_ticks and cum[k + 1] > cum[k]:
                pidx = np.arange(cum[k], cum[k + 1])
                parts.append(batches(rec, pidx, tables, False)[2])
            self.batches.append(RecordBatch.concat(parts))
        self.first_lsn = np.full(n_ticks, -1, np.int64)
        self.appended_at = np.full(n_ticks, np.nan)
        self.t0: Optional[float] = None
        self.error: Optional[BaseException] = None
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None

    def due(self, k) -> np.ndarray:
        return self.t0 + np.asarray(k) * self.tick_s

    def start(self, log, t0: float) -> None:
        self.t0 = t0
        self._thread = threading.Thread(target=self._run, args=(log,),
                                        daemon=True, name="bench.stream")
        self._thread.start()

    def _run(self, log) -> None:
        try:
            for k, b in enumerate(self.batches):
                wait = self.t0 + k * self.tick_s - time.perf_counter()
                if wait > 0 and self._stop.wait(wait):
                    return
                if len(b):
                    self.first_lsn[k] = log.append(b)[0]
                self.appended_at[k] = time.perf_counter()
        except BaseException as e:          # reported by the harness
            self.error = e

    def join(self, timeout: float) -> bool:
        self._thread.join(timeout)
        return not self._thread.is_alive()

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(5.0)


# ------------------------------------------------------------ dashboard mix
def query_mix(mix: List[dict], n_units: int):
    """The dashboard's query set as (kind, arg) pairs: ``per_unit`` kinds
    cycle over the units."""
    out = []
    for item in mix:
        for i in range(item["count"]):
            if item.get("per_unit"):
                out.append((item["kind"], i % n_units))
            else:
                out.append((item["kind"], item.get("arg", -1)))
    return out
