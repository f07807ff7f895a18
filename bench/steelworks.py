"""The steelworks deployments (arXiv:1907.06723 §4) under the benchmark's
traffic: builds the deployment from its configuration file, warms up every
shape the cell's traffic uses, drives the measured window, and hands the
harness its end-to-end numbers, the traced readings and the comparison with
the plain reference.

The window drives ``ConcurrentCluster`` fed from the CDC log, with
``MaterializedViewEngine`` attached and, for dashboard traffic,
``BatchedReportServer`` in front of it. Two traffic modes:

* ``stream``: an open-loop generator appends every ``tick_ms`` (gen.Stream)
  while the cluster extracts, loads and folds; visible latency is the time
  from a production record's due time to the view epoch that made it
  queryable, over every record due in the window. Dashboard traffic adds
  bursts of queries, timed from the burst's due time to the answer.
* ``backlog``: the change log is filled and extracted to the broker at
  set-up with more records than the window can drain; the window counts
  the records loaded. A backlog that empties before the window ends fails
  the run.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, Optional

import numpy as np

from bench import gen, reference
from bench.harness import BenchError, sleep_until

STAGE_SPANS = ("ingest.fetch", "transform.dispatch", "load.commit",
               "serving.fold", "query.batch")
DRAIN_TIMEOUT_S = 60.0


def pow2_at_least(n: int) -> int:
    return 1 << max(0, int(n) - 1).bit_length()


@dataclasses.dataclass
class Run:
    """What one run hands the harness and the per-layer metric readers."""

    config: dict
    window: tuple                       # (t_open, t_close), host clock
    e2e: Dict[str, float]
    attempted: int
    failed: int
    compared: Dict[str, float]
    notes: Dict[str, object]
    spans: List[tuple] = dataclasses.field(default_factory=list)
    gen_lateness_s: Optional[np.ndarray] = None
    transform_calls: List[tuple] = dataclasses.field(default_factory=list)
    device_trace: Optional[dict] = None
    peaks: Optional[dict] = None


# ------------------------------------------------------------------- build
def etl_config(c: dict, cache_slots: int = 4096):
    from repro.configs.dod_etl import steelworks_config
    cfg = steelworks_config(n_partitions=c["n_partitions"],
                            complex_model=c["schema"] == "isa95-normalized",
                            backend=c["backend"])
    if cfg.n_business_keys != c["n_units"]:
        raise BenchError("n_units must equal n_partitions in steelworks_config")
    return dataclasses.replace(cfg, cache_slots=cache_slots)


def cache_slots(c: dict, n_records: int) -> int:
    """Slots per cache: the next power of two that keeps the busiest
    worker's quality cache, holding the master history and every record's
    inspection by the end of the run, at or under the configuration's load
    factor. No cache grows
    (and recompiles) during a run; the busiest worker is the one the static
    routing gives the most units."""
    from repro.core import DODETLPipeline, SourceDatabase
    probe = DODETLPipeline(etl_config(c), SourceDatabase(),
                           n_workers=c["n_workers"])
    share = max(len(w.assigned_business_keys(c["n_units"]))
                for w in probe.workers) / c["n_units"]
    rows = int(np.ceil(share * n_records))
    return pow2_at_least(int(np.ceil(rows / c["max_load_factor"])))


def warm_up(pipe, engine, c: dict, dashboard: bool) -> None:
    """Compile every shape this cell's traffic reaches: the transform at
    each dispatch bucket (a dispatch never exceeds the late buffer's
    capacity), the view fold at each (rows, live segments) bucket a load
    can produce, and for dashboards the gather at each batch bucket."""
    from repro.core.backend import FOLD_BLOCK
    be = pipe.backend
    w = pipe.workers[0]
    size, top = 128, pow2_at_least(pipe.cfg.buffer_capacity)
    while size <= top:
        block = be.transform_block(np.full((size, 8), -1.0, np.float32),
                                   w.equipment, w.quality,
                                   join_depth=c["join_depth"],
                                   n_units=c["n_units"])
        block.to_host()
        size *= 2
    shapes = {(s.n_segments, s.n_lanes) for s in engine.specs}
    for n_seg, n_lanes in sorted(shapes):
        combos = set()
        rows = 8
        while rows <= FOLD_BLOCK:
            k = 1
            while True:
                active = min(k, n_seg, rows)
                combos.add((rows, active, min(n_seg, max(
                    8, pow2_at_least(active)))))
                if active >= min(n_seg, rows):
                    break
                k *= 2
            rows *= 2
        seen = set()
        for rows, active, width in sorted(combos):
            if (rows, width) in seen:
                continue
            seen.add((rows, width))
            be.fold_segments(np.arange(rows, dtype=np.int64) % active,
                             np.zeros((rows, n_lanes), np.float32), n_seg)
    if dashboard:
        buckets, b = [], 8
        while b <= 4096:
            buckets.append(b)
            b *= 2
        engine.prewarm_read(batch_buckets=tuple(buckets))


class VisibleRecorder:
    """Takes the place of the view engine's staleness reservoir: the engine
    hands it, at each epoch swap, ``swap_time - event_time`` for every
    record the swap made queryable. Kept whole (the engine's own reservoir
    subsamples past 65,536), with the swap time, so each record's CDC
    append stamp, and from it its due time, can be recovered."""

    def __init__(self, engine):
        from repro.core.metrics import LatencyRecorder
        self._engine = engine
        self._base = LatencyRecorder()      # what a registry may read
        self.parts: List[tuple] = []

    def add(self, samples: np.ndarray) -> None:
        # called by fold_pending right after the swap, under its fold lock
        self.parts.append((self._engine.snapshot().published_at,
                           np.asarray(samples, np.float64)))

    def __getattr__(self, name):
        return getattr(self._base, name)

    def pairs(self):
        """(event_time, visible_time) per record made visible."""
        if not self.parts:
            return np.zeros(0), np.zeros(0)
        vis = np.concatenate([np.full(len(s), t) for t, s in self.parts])
        lat = np.concatenate([s for _, s in self.parts])
        return vis - lat, vis


def stamped_front(server, max_batch: int, max_wait_ms: float):
    """``BatchedReportServer`` that notes when each query's batch was
    answered."""
    from repro.serving import BatchedReportServer

    class StampedFront(BatchedReportServer):
        def __init__(self, *a, **k):
            super().__init__(*a, **k)
            self.answered: Dict[int, float] = {}

        def _answer(self, batch):
            super()._answer(batch)
            t = time.perf_counter()
            for tk in batch:
                self.answered[id(tk)] = t

    return StampedFront(server, max_batch=max_batch, max_wait_ms=max_wait_ms)


# --------------------------------------------------------------------- run
def run(spec: dict, seed: int, seconds: float, trace: bool, t_process: float,
        peaks: Optional[dict] = None, control: bool = False,
        fault=None, overrides: Optional[dict] = None,
        profiler_factory=None) -> Run:
    """One run of a cell. ``control`` compares the bfloat16 reference in
    the program's place; ``fault`` (tests) breaks the timed path after
    the deployment is built; ``overrides`` (tests) shrink the traffic."""
    from repro.core import DODETLPipeline, SourceDatabase
    from repro.runtime.cluster import ConcurrentCluster
    from repro.serving import (MaterializedViewEngine, ReportQuery,
                               ReportServer, steelworks_views)
    from bench import harness

    c = spec["config"]
    tr = dict(spec["traffic"], **(overrides or {}))
    nu = c["n_units"]
    vcfg = c["views"]
    mode = tr["mode"]
    dashboard = "queries" in tr
    warmup = float(tr["warmup_s"])
    tick_s = tr.get("tick_ms", 10) * 1e-3

    if mode == "stream":
        rate = tr.get("rate_records_s") or tr["rate_x_knee"] * c["knee_records_s"]
        n_prod_ticks = int(round((warmup + seconds) / tick_s))
        n = int(np.floor(rate * tick_s * n_prod_ticks))
    else:
        fill = (tr.get("fill_records_s")
                or tr["fill_x_drain"] * c["drain_records_s"])
        n = int(np.ceil(fill * (warmup + seconds)))
    n_hist = int(c.get("master_history_records", 0))
    cfg = etl_config(c, cache_slots(c, n + n_hist))
    src = SourceDatabase()
    tracer = harness.annotated_tracer() if trace else None
    pipe = DODETLPipeline(cfg, src, n_workers=c["n_workers"],
                          join_depth=c["join_depth"], tracer=tracer)
    if pipe.backend.name != c["backend"]:
        raise BenchError(f"backend {pipe.backend.name}, configuration "
                         f"states {c['backend']}")
    engine = MaterializedViewEngine(steelworks_views(
        nu, vcfg["n_shifts"], vcfg["shift_len"], vcfg["n_windows"],
        vcfg["window_len"]))
    recorder = VisibleRecorder(engine)
    engine.staleness_recorder = recorder
    front = None
    if dashboard:
        q = tr["queries"]
        front = stamped_front(ReportServer(engine), q["max_batch"],
                              q["max_wait_ms"])
    cluster = ConcurrentCluster(pipe, poll_cdc=(mode == "stream"),
                                serving=front or engine)
    tables = gen.Tables([t.name for t in cfg.tables])

    # ---- data from the seed
    rec = gen.make_records(n, nu, c["late_master_frac"], seed)
    late_ticks = [int(round(x / tr.get("tick_ms", 10)))
                  for x in c["late_delay_ms"]]
    stream = (gen.Stream(rec, tables, rate, tick_s,
                         n_prod_ticks, late_ticks, seed)
              if mode == "stream" else None)
    if n_hist:
        # the plant's master data so far, dumped into every worker's caches
        # the way a worker starts (the paper's initial snapshot dump)
        for b in gen.history(n, n_hist, nu, rec.status, tables, seed):
            src.log.append(b)
        pipe.extract()
        pipe.bootstrap_caches()
    if mode == "backlog":
        idx = np.arange(n)
        on, late = idx[~rec.late], idx[rec.late]
        eq, qu, prod = gen.batches(rec, on, tables, False)
        leq, lqu, _ = gen.batches(rec, late, tables, True)
        for b in (eq, qu, gen.batches(rec, idx, tables, False)[2], leq, lqu):
            if len(b):
                src.log.append(b)
        pipe.extract()
    bursts = []
    if dashboard:
        mix = gen.query_mix(q["mix"], nu)
        queries = [ReportQuery(kind, view=(reference.VIEW_ARGS[arg]
                                           if kind == "view" else None),
                               unit=(arg if kind == "oee" and arg >= 0
                                     else None),
                               k=(arg if kind == "top_downtime" else 5))
                   for kind, arg in mix]
        r = gen.rng(seed, 4)
        n_bursts = int(np.ceil((warmup + seconds) / q["period_s"]))
        bursts = [r.permutation(len(queries)) for _ in range(n_bursts)]

    warm_up(pipe, engine, c, dashboard)
    sizing = {"cache_slots": cfg.cache_slots,
              "cache_device_bytes": sum(
                  a.nbytes for w in pipe.workers
                  for t in (w.equipment, w.quality) for a in t.device_state()),
              "master_rows_setup": sum(w.equipment.n_rows + w.quality.n_rows
                                     for w in pipe.workers)}
    initial_slots = [(w.equipment.n_slots, w.quality.n_slots)
                     for w in pipe.workers]
    if fault is not None:
        fault(pipe, engine, front)

    transform_calls: List[tuple] = []
    if trace:
        be = pipe.backend
        inner = be.transform_block

        def recorded(prod, *a, **k):
            transform_calls.append((time.perf_counter(), len(prod)))
            return inner(prod, *a, **k)
        be.transform_block = recorded

    # ---- window
    tickets: List[tuple] = []          # (ticket, due, kind, arg)
    dash_thread = None
    prof = profiler_factory(STAGE_SPANS) if trace else None
    cluster.start()
    t0 = time.perf_counter() + 0.05
    t_open, t_close = t0 + warmup, t0 + warmup + seconds
    if stream is not None:
        stream.start(src.log, t0)
    if dashboard:
        import threading
        stop_dash = threading.Event()

        def dash():
            for j, order in enumerate(bursts):
                due = t0 + j * q["period_s"]
                if due >= t_close:
                    return
                wait = due - time.perf_counter()
                if wait > 0 and stop_dash.wait(wait):
                    return
                for i in order:
                    kind, arg = mix[i]
                    tickets.append((front.submit(queries[i]), due, kind, arg))
        dash_thread = threading.Thread(target=dash, daemon=True,
                                       name="bench.dash")
        dash_thread.start()

    def produced() -> int:
        """Production records handed to the system so far."""
        if stream is None:
            return n
        done = int(np.isfinite(stream.appended_at[:stream.prod_ticks]).sum())
        return int(np.floor(rate * tick_s * done))

    def check():
        for rt in cluster.runtimes.values():
            if rt.error is not None:
                raise rt.error
        if stream is not None and stream.error is not None:
            raise stream.error

    try:
        if prof is not None:
            sleep_until(t_open - 1.0, check)
            prof.start()
        sleep_until(t_open, check)
        loaded_open = pipe.warehouse.rows_loaded
        produced_open = produced()
        unfolded_open = loaded_open - engine.snapshot().rows_folded
        t_open = time.perf_counter()
        if prof is not None:
            with prof.window():
                sleep_until(t_close, check)
                t_close = time.perf_counter()
        else:
            sleep_until(t_close, check)
            t_close = time.perf_counter()
        loaded_close = pipe.warehouse.rows_loaded
        produced_close = produced()
        unfolded_close = loaded_close - engine.snapshot().rows_folded
        backlog_left = None
        if mode == "backlog":
            # the broker lag at the window's close, traced or not. Nothing
            # after a backlog's window needs loading, so the stage threads
            # stop here (stop_all joins them): neither the trace's
            # reduction nor the final fold waits on records loaded late
            backlog_left = int(cluster._operational_lag())
            for rt in cluster.runtimes.values():
                rt.stop.set()
        device_trace = prof.stop_and_reduce() if prof is not None else None

        # ---- after the window: let every due record and query finish
        notes: Dict[str, object] = dict(
            sizing, backlog_open=produced_open - loaded_open,
            backlog_close=produced_close - loaded_close,
            unfolded_open=unfolded_open, unfolded_close=unfolded_close)
        if mode == "backlog":
            # run.py refuses a run whose backlog emptied in the window
            notes["backlog_left"] = backlog_left
        else:
            if not stream.join(DRAIN_TIMEOUT_S):
                raise BenchError("the generator did not finish")
            deadline = time.perf_counter() + DRAIN_TIMEOUT_S
            while (pipe.warehouse.rows_loaded < stream.n_records
                   and time.perf_counter() < deadline):
                check()
                time.sleep(0.01)
        if dash_thread is not None:
            stop_dash.set()
            dash_thread.join(5.0)
            deadline = time.perf_counter() + DRAIN_TIMEOUT_S
            while (not all(t.done() for t, *_ in tickets)
                   and time.perf_counter() < deadline):
                time.sleep(0.01)
        deadline = time.perf_counter() + DRAIN_TIMEOUT_S
        while (mode == "stream" and time.perf_counter() < deadline
               and engine.snapshot().rows_folded < pipe.warehouse.rows_loaded):
            time.sleep(0.01)
        import jax
        notes["memory_peak_bytes"] = harness.device_info(
            jax.devices(), 1)["memory_peak_bytes"]
    finally:
        if stream is not None:
            stream.stop()
        cluster.stop_all()
        if trace:
            del pipe.backend.transform_block
    spans = list(tracer.events()) if tracer is not None else []

    # ---- end-to-end numbers
    e2e: Dict[str, float] = {"setup_s": t_open - t_process}
    window_len = t_close - t_open
    attempted = failed = 0
    gen_late = None
    if mode == "backlog":
        e2e["loaded_records_s"] = (loaded_close - loaded_open) / window_len
        attempted = loaded_close - loaded_open
    else:
        ticks = np.arange(len(stream.batches))
        appended = stream.first_lsn >= 0
        stamps = src.log.event_times(stream.first_lsn[appended])
        due = stream.due(ticks[appended])
        in_win = (due >= t_open) & (due < t_close)
        gen_late = (stamps - due)[in_win]
        ev, vis = recorder.pairs()
        pos = np.clip(np.searchsorted(stamps, ev), 0, len(stamps) - 1)
        prev = np.clip(pos - 1, 0, len(stamps) - 1)
        pos = np.where(np.abs(stamps[prev] - ev) < np.abs(stamps[pos] - ev),
                       prev, pos)
        rec_due = due[pos]
        win = (rec_due >= t_open) & (rec_due < t_close)
        lat_ms = (vis[win] - rec_due[win]) * 1e3
        prod_due = stream.due(np.repeat(
            np.arange(stream.prod_ticks),
            [_n_prod(b, tables) for b in stream.batches[:stream.prod_ticks]]))
        attempted = int(((prod_due >= t_open) & (prod_due < t_close)).sum())
        failed = max(0, attempted - len(lat_ms))
        notes["visible_samples"] = int(len(lat_ms))
        # a record never made visible misses every latency limit
        lat_ms = np.concatenate([lat_ms, np.full(failed, np.inf)])
        if len(lat_ms):
            e2e["visible_p50_ms"] = float(np.percentile(lat_ms, 50))
            e2e["visible_p95_ms"] = float(np.percentile(lat_ms, 95))
    if dashboard:
        due_q = np.array([d for _, d, _, _ in tickets])
        inq = (due_q >= t_open) & (due_q < t_close)
        ans = np.array([front.answered.get(id(t), np.nan)
                        for t, *_ in tickets])
        qlat = (ans - due_q)[inq] * 1e3
        attempted += int(inq.sum())
        failed += int(np.isnan(qlat).sum())
        if len(qlat):      # an unanswered query misses every limit
            e2e["query_p95_ms"] = float(np.percentile(
                np.where(np.isnan(qlat), np.inf, qlat), 95))
        notes["queries"] = int(inq.sum())

    compared = compare(c, rec, pipe, engine, mode, tickets, initial_slots,
                       control)
    if control:     # the sound readings of the same run, for the limits
        notes["compared_sound"] = compare(c, rec, pipe, engine, mode,
                                          tickets, initial_slots, False)
    return Run(config=c, window=(t_open, t_close), e2e=e2e,
               attempted=attempted, failed=failed,
               compared=compared, notes=notes, spans=spans,
               gen_lateness_s=gen_late, transform_calls=transform_calls,
               device_trace=device_trace, peaks=peaks)


def _n_prod(batch, tables) -> int:
    return int((batch.table_id == tables.production).sum())


# ----------------------------------------------------------------- compare
def compare(c: dict, rec, pipe, engine, mode: str, tickets, initial_slots,
            control: bool) -> Dict[str, float]:
    """The numbers that decide ``correct``, each against the plain
    reference (``reference.py``) in float64:

    * ``bad_records``: production records not loaded exactly once, or
      loaded with the wrong unit, window or validity. A backlog run must
      have loaded, per unit, exactly the first records of that unit, as
      many in all as the committed offsets say.
    * ``fact_err``: widest relative gap of a loaded fact's KPIs and
      segment times (join + OEE transform).
    * ``view_rows_off``: final epoch's rows and per-segment counts against
      the loaded facts (every loaded fact in the view epoch, once).
    * ``view_err``: widest relative gap of the final view tables (fold).
    * ``rollup_err``: widest relative gap of the per-unit KPI rollup the
      transform dispatch carries.
    * ``answer_err``, ``answer_rows_off`` (dashboard): each batched answer
      against the reference answer at the epoch it pinned (gather).
    * ``caches_grown``: caches whose slot count changed after set-up.

    With ``control`` the bfloat16 reference stands in for the program's
    facts, views, rollup and answers; which records each holds stays the
    program's."""
    nu, vcfg = c["n_units"], c["views"]
    wh = pipe.warehouse
    view = wh.read_view()
    chunks = [np.asarray(ch) for ch in view.chunks]
    got = (np.concatenate(chunks) if chunks
           else np.zeros((0, reference.FACT_WIDTH), np.float32))
    out: Dict[str, float] = {}

    # which record each loaded fact is: (unit, t_start) names it exactly
    idx = record_index(got)
    named = ((idx >= 0) & (idx < len(rec))
             & (gen.T0 + gen.TICK * idx == got[:, 1].astype(np.float64)))
    idx = np.where(named, idx, 0)
    ok = (named & (got[:, 0] == rec.unit[idx])
          & (got[:, 2] == rec.t_end[idx].astype(np.float32))
          & (got[:, 9] == 1.0))
    counts = np.bincount(idx[named], minlength=len(rec))
    if mode == "stream":
        expected = np.ones(len(rec), bool)
    else:
        # per unit, the loaded records must be that unit's first ones
        expected = np.zeros(len(rec), bool)
        for u in range(nu):
            mine = np.flatnonzero(rec.unit == u)
            k = int(counts[mine].sum())
            expected[mine[:k]] = True
        group = {w.name: w.group for w in pipe.workers}
        committed = sum(pipe.queue.committed(group[owner], t, p)
                        for t in pipe.operational_topics
                        for p, owner in pipe.assignment.assignment.items())
    bad = int((~ok).sum()) + int(np.abs(counts - expected).sum())
    if mode == "backlog":
        bad += abs(int(committed) - int(counts.sum()))
    out["bad_records"] = float(bad)

    # reference facts of the loaded records, float64
    dtype = reference_dtype(control)
    li = idx[ok]
    want = ref_facts(rec, li)
    cand = ref_facts(rec, li, dtype) if control else got[ok]
    cols = slice(3, 9)
    out["fact_err"] = reference.rel_err(cand[:, cols], want[:, cols])

    # the final epoch's views against the loaded facts
    snap = engine.snapshot()
    ref_fold = reference.ViewFold(nu, vcfg)
    ref_fold.add(want)
    ctl_fold = None
    if control:
        ctl_fold = reference.ViewFold(nu, vcfg, dtype)
        ctl_fold.add(cand)
    rows_off = abs(snap.rows_folded - len(got))
    verr = 0.0
    for name, table in ref_fold.tables.items():
        mine = (ctl_fold.tables[name] if control
                else np.asarray(snap.view(name).table))
        rows_off += int(np.abs(mine[:, 0].astype(np.float64)
                               - table[:, 0]).sum())
        verr = max(verr, reference.rel_err(mine[:, 1:], table[:, 1:]))
    out["view_rows_off"] = float(rows_off)
    out["view_err"] = verr
    want_roll = reference.kpi_rollup(want, nu)
    roll = (reference.kpi_rollup(cand, nu, dtype) if control
            else wh.kpi_running())
    out["rollup_err"] = (float("inf") if roll is None
                         else reference.rel_err(roll, want_roll))

    if tickets:
        aerr, arows = compare_answers(c, rec, chunks, tickets, control)
        out["answer_err"] = aerr
        out["answer_rows_off"] = float(arows)

    out["caches_grown"] = float(sum(
        (w.equipment.n_slots, w.quality.n_slots) != s
        for w, s in zip(pipe.workers, initial_slots)))
    return out


def record_index(facts: np.ndarray) -> np.ndarray:
    """The record a fact row came from, by its window start."""
    return np.rint((facts[:, 1].astype(np.float64) - gen.T0) / gen.TICK
                   ).astype(np.int64)


def ref_facts(rec, li: np.ndarray, dtype=np.float64) -> np.ndarray:
    """Reference facts of records ``li``: each joined with its unit's
    status row and its own inspection."""
    n, u = len(li), rec.unit[li]
    return reference.facts(
        rec.production(li), status=rec.status[u],
        e_start=np.full(n, gen.EQ_START), e_end=np.full(n, gen.EQ_END),
        max_speed=np.full(n, gen.MAX_SPEED), planned=np.full(n, gen.PLANNED),
        defects=rec.defects[li], scrap=rec.scrap[li], dtype=dtype)


def reference_dtype(control: bool):
    if not control:
        return np.float64
    import ml_dtypes
    return ml_dtypes.bfloat16


def compare_answers(c: dict, rec, chunks, tickets, control: bool):
    """Every answered query against the reference answer at the epoch it
    pinned. The epoch's facts are the first ``deltas_folded`` committed
    chunks (the engine folds deltas in commit order); the reference
    recomputes those facts from the generated records and folds them."""
    nu, vcfg = c["n_units"], c["views"]
    dtype = reference_dtype(control)
    need = sorted({t.snapshot.deltas_folded for t, *_ in tickets})
    fold = reference.ViewFold(nu, vcfg)
    cfold = reference.ViewFold(nu, vcfg, dtype) if control else None
    at: Dict[int, tuple] = {}
    rows = 0
    j = 0
    for d in need:
        if d > j:
            li = record_index(np.concatenate(chunks[j:d]))
            fold.add(ref_facts(rec, li))
            if cfold is not None:
                cfold.add(ref_facts(rec, li, dtype))
            rows += len(li)
            j = d
        at[d] = (fold.snapshot(), cfold.snapshot() if cfold else None, rows)
    err, rows_off = 0.0, 0
    memo: Dict[tuple, dict] = {}
    for t, _due, kind, arg in tickets:
        if not t.done():
            continue
        d = t.snapshot.deltas_folded
        ref_tables, ctl_tables, ref_rows = at[d]
        key = (d, kind, arg)
        if key not in memo:
            memo[key] = reference.answer(kind, arg, ref_tables)
            if control:
                memo[key + ("ctl",)] = reference.answer(kind, arg, ctl_tables)
        want = memo[key]
        rep = t.result(0)
        rows_off += abs(int(rep.rows) - ref_rows) + abs(
            int(rep.epoch) - int(t.snapshot.epoch))
        have = (memo[key + ("ctl",)] if control
                else program_answer(kind, arg, rep, want))
        for k, w in want.items():
            if kind == "top_downtime" and k in ("downtime", "uptime"):
                continue
            err = max(err, reference.rel_err(have[k], w))
        if kind == "top_downtime" and not control:
            units = np.asarray(rep.data["unit"], np.int64)
            err = max(err,
                      reference.rel_err(rep.data["downtime_s"],
                                        want["downtime"][units]),
                      reference.rel_err(rep.data["uptime_s"],
                                        want["uptime"][units]))
    return err, rows_off


def program_answer(kind: str, arg: int, rep, want: dict) -> dict:
    """A program ``Report`` in the reference answer's layout."""
    d = rep.data
    if kind == "oee":
        lanes = ("availability", "performance", "quality", "oee")
        return {"means": np.array([d[k] for k in lanes]),
                "rows": np.array([d["rows"]])}
    if kind == "top_downtime":
        return {"ranked_downtime": np.asarray(d["downtime_s"])}
    if kind in ("production_rate", "production_curve", "shift_report",
                "view"):
        return {k: np.asarray(d[k]) for k in want}
    if kind == "kpi_rollup":
        return {"kpi_rollup": np.asarray(d["kpi_rollup"])}
    raise KeyError(kind)
