"""The TPC-DI DimTrade deployment (TPC Benchmark DI v1.1.0, the incremental
update of DimTrade from Trade.txt) under the benchmark's backlog traffic:
builds the deployment from its configuration file, makes its data from
``--seed``, warms up every shape, drives the measured window through
``ConcurrentCluster`` and compares the final DimTrade with the plain
reference (``tpcdi_reference.py``).

The data, all int32 and made in bulk:

* DimAccount: ``n_accounts`` accounts with a first version before the
  batch, a share with a second one in effect from a second inside the
  log's span (TPC-DI's batch updates DimAccount before DimTrade); dumped
  into the partitioned caches at set-up as a worker starts (too large to
  publish);
* DimSecurity: ``n_securities`` rows, replicated into every worker;
* the change log, in commit-time order (``txn_time``: milliseconds since
  the batch's start, at ``source_records_s`` records per second): Trade
  CDC rows, each trade an insert (SBMT for a market order, PNDG otherwise)
  then an update 0-2 s later (CMPT, or CNCL for ``cancel_frac``), and
  account CDC (``account_cdc_frac`` of the trade rows): half new accounts,
  whose version lands ``late_account_delay_ms`` after their first trades
  (its EffectiveDate is the first trade's ``T_DTS``), half new versions of
  existing accounts, in effect from the second after they commit.

``T_DTS`` is ``t0`` plus the commit time's whole seconds. Ids are DIGen
style sequential integers; symbols and executor names dictionary codes.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, Optional

import numpy as np

from bench import tpcdi_reference as reference
from bench.gen import rng
from bench.harness import BenchError, sleep_until
from bench.steelworks import Run, pow2_at_least

STAGE_SPANS = ("ingest.fetch", "transform.dispatch", "load.commit",
               "load.upsert")
TRADE, ACCOUNT, SECURITY = 0, 1, 2       # table ids: the configuration's order
TID_BASE = 200_000_000                   # trades of the historical load first
DSN_BASE = 1_000_000_000
SK_ACCOUNT_BASE = 1_000_000
SK_SECURITY_BASE = 10_000_000
SK_COMPANY_BASE = 20_000_000
SK_BROKER_BASE = 30_000_000
SK_CUSTOMER_BASE = 40_000_000


@dataclasses.dataclass
class Data:
    """One run's data: the change log and every dimension row."""

    log: object                 # RecordBatch, commit-time order
    accounts: np.ndarray        # i32 [m, 8]: every DimAccount version
    history: int                # the first ``history`` versions: dumped at set-up
    securities: np.ndarray      # i32 [k, 8]
    n_trade_rows: int
    n_new_accounts: int


def make_data(c: dict, n: int, seed: int) -> Data:
    """``n`` change-log records (trade rows plus account CDC) and the
    dimensions they join, from ``seed``."""
    from repro.core.records import OP_INSERT, OP_UPDATE, RecordBatch
    r = rng(seed, 21)
    t0 = int(c["t0"])
    n_acct = int(c["n_accounts"])
    n_trade_rows = 2 * int(round(n / (1 + c["account_cdc_frac"]) / 2))
    k = n_trade_rows // 2
    n_cdc = n - n_trade_rows
    n_new = int(round(n_cdc * c["new_account_share"]))
    n_upd = n_cdc - n_new
    span_ms = int(np.ceil(n_trade_rows / c["source_records_s"] * 1000))

    # ---- DimAccount as the batch finds it: a first version, and a share
    # with a second one in effect from a second inside the log's span (the
    # batch updates DimAccount before DimTrade, so a trade before that
    # second joins the first version although the second is cached)
    two = np.flatnonzero(r.random(n_acct) < c["history_second_version_frac"])
    hist_aid = np.concatenate([np.arange(n_acct), two])
    day = 86400
    hist_eff = np.concatenate([
        t0 - r.integers(30 * day, 3650 * day, n_acct),
        t0 + r.integers(0, span_ms // 1000 + 1, len(two))])
    customer = SK_CUSTOMER_BASE + r.integers(0, c["n_customers"],
                                             n_acct + n_new)

    # ---- trades: inserts in commit order, each update 0-2 s later
    i_txn = np.sort(r.integers(0, span_ms, k))
    lo, hi = c["update_delay_ms"]
    u_txn = i_txn + r.integers(lo, hi + 1, k)
    ca = r.integers(0, n_acct, k)
    # new accounts: their first 1-2 trades, then their version, late
    firsts = r.choice(k, size=2 * n_new, replace=False)
    n_firsts = r.integers(1, 3, n_new)
    owner = np.repeat(np.arange(n_new), 2)
    keep = np.ones(2 * n_new, bool)
    keep[1::2] = n_firsts == 2
    firsts, owner = firsts[keep], owner[keep]
    ca[firsts] = n_acct + owner
    first_txn = np.full(n_new, np.iinfo(np.int64).max)
    last_txn = np.zeros(n_new, np.int64)
    np.minimum.at(first_txn, owner, i_txn[firsts])
    np.maximum.at(last_txn, owner, i_txn[firsts])
    dlo, dhi = c["late_account_delay_ms"]
    new_txn = last_txn + r.integers(dlo, dhi + 1, n_new)
    new_eff = t0 + first_txn // 1000
    # new versions of existing accounts, in effect from the next second
    upd_aid = r.choice(n_acct, size=n_upd, replace=False)
    upd_txn = r.integers(0, span_ms, n_upd)
    upd_eff = t0 + upd_txn // 1000 + 1

    aid = np.concatenate([hist_aid, n_acct + np.arange(n_new), upd_aid])
    eff = np.concatenate([hist_eff, new_eff, upd_eff])
    m = len(aid)
    accounts = np.stack([
        SK_ACCOUNT_BASE + np.arange(m), aid,
        SK_BROKER_BASE + r.integers(0, c["n_brokers"], m), customer[aid],
        np.zeros(m, np.int64), r.integers(0, 1000, m), r.integers(0, 3, m),
        eff], axis=-1).astype(np.int32)
    n_hist = len(hist_aid)

    n_sec = int(c["n_securities"])
    securities = np.stack([
        SK_SECURITY_BASE + np.arange(n_sec), np.arange(n_sec),
        r.integers(0, 4, n_sec), np.zeros(n_sec, np.int64),
        r.integers(0, 1 << 20, n_sec), r.integers(0, 4, n_sec),
        SK_COMPANY_BASE + r.integers(0, c["n_companies"], n_sec),
        r.integers(1 << 20, 1 << 30, n_sec)], axis=-1).astype(np.int32)

    # ---- the trade rows
    tt = r.integers(0, 5, k)                   # TMB TMS TSL TLS TLB
    st_i = np.where(tt < 2, 4, 3)              # SBMT market, PNDG otherwise
    st_u = np.where(r.random(k) < c["cancel_frac"], 2, 1)   # CNCL / CMPT
    sym = r.integers(0, n_sec, k)
    qty = r.integers(100, 801, k)
    bid = r.integers(100, 200_000, k)
    cash = (r.random(k) < 0.8).astype(np.int64)
    ex = r.integers(0, c["n_exec_names"], k)
    chrg = r.integers(0, 2_000, k)
    price = np.where(st_u == 1, bid + r.integers(-500, 501, k), -1)
    comm = np.where(st_u == 1, r.integers(0, 5_000, k), -1)
    tax = np.where(st_u == 1, r.integers(0, 3_000, k), -1)
    tid = TID_BASE + np.arange(k)
    null = np.full(k, -1)

    def rows(flag, txn, st, tprice, tcomm, ttax):
        return np.stack([np.full(k, flag), np.zeros(k, np.int64), tid,
                         t0 + txn // 1000, st, tt, cash, sym, qty, bid, ca,
                         ex, tprice, chrg, tcomm, ttax], axis=-1)

    trades = np.concatenate([rows(0, i_txn, st_i, null, null, null),
                             rows(1, u_txn, st_u, price, comm, tax)])
    acct_rows = np.zeros((m - n_hist, 16), np.int64)
    acct_rows[:, :8] = accounts[n_hist:]
    # ---- the log, in commit-time order
    txn = np.concatenate([i_txn, u_txn, new_txn, upd_txn])
    table = np.r_[np.full(2 * k, TRADE), np.full(m - n_hist, ACCOUNT)]
    order = np.lexsort((table, txn))
    payload = np.concatenate([trades, acct_rows])[order].astype(np.int32)
    is_trade = table[order] == TRADE
    payload[is_trade, 1] = DSN_BASE + np.arange(int(is_trade.sum()))
    op = np.r_[np.full(k, OP_INSERT), np.full(k, OP_UPDATE),
               np.full(m - n_hist, OP_INSERT)][order]
    row_key = np.r_[tid, tid, accounts[n_hist:, 0]][order]
    bkey = np.r_[ca, ca, accounts[n_hist:, 1]][order]
    log = RecordBatch(table_id=table[order].astype(np.int32),
                      op=op.astype(np.int32), row_key=row_key.astype(np.int64),
                      business_key=bkey.astype(np.int64),
                      txn_time=txn[order].astype(np.int64),
                      lsn=np.zeros(len(order), np.int64), payload=payload)
    return Data(log=log, accounts=accounts, history=n_hist,
                securities=securities, n_trade_rows=2 * k,
                n_new_accounts=n_new)


# ------------------------------------------------------------------- build
def etl_config(c: dict, account_slots: int, security_slots: int):
    from repro.configs.dod_etl import tpcdi_dimtrade_config
    cfg = tpcdi_dimtrade_config(
        n_accounts=c["n_accounts"] + c["max_new_accounts"],
        n_partitions=c["n_partitions"], backend=c["backend"])
    tables = tuple(dataclasses.replace(t, slots=security_slots)
                   if t.name == "DimSecurity" else t for t in cfg.tables)
    return dataclasses.replace(cfg, tables=tables, cache_slots=account_slots)


def cache_slots(c: dict, data: Data) -> tuple:
    """(DimAccount, DimSecurity) slots per worker: the next power of two
    that keeps the busiest worker's account versions (history and every
    version in the log) and the whole DimSecurity at or under the load
    factor. No cache grows during a run."""
    from repro.core import DODETLPipeline, SourceDatabase
    probe = DODETLPipeline(etl_config(c, 16, 16), SourceDatabase(),
                           n_workers=c["n_workers"])
    parts = probe.current_routing().partition_of(
        data.accounts[:, 1].astype(np.int64))
    per_part = np.bincount(parts, minlength=c["n_partitions"])
    busiest = max(int(per_part[list(w.partitions)].sum())
                  for w in probe.workers)
    lf = c["max_load_factor"]
    return (pow2_at_least(int(np.ceil(busiest / lf))),
            pow2_at_least(int(np.ceil(len(data.securities) / lf))))


def warm_up(pipe) -> None:
    """Upload every worker's caches, work out its business keys, compile
    the versioned cache's in-place device update at each size, and the
    DimTrade kernel at each dispatch bucket (a dispatch never exceeds the
    late buffer's capacity)."""
    be = pipe.backend
    for w in pipe.workers:
        for cache in w.caches:
            cache.snapshot_view(be.device)
        # the business keys a worker's pump filters by, worked out once
        w.assigned_business_keys(pipe.cfg.n_business_keys)
    w = pipe.workers[0]
    if be.device:
        w.caches[0].compile_updates()
    size, top = 128, pow2_at_least(pipe.cfg.buffer_capacity)
    while size <= top:
        be.transform_block(np.full((size, 16), -1, np.int32), *w.caches,
                           schema=pipe.cfg.schema).to_host()
        size *= 2


# --------------------------------------------------------------------- run
def run(spec: dict, seed: int, seconds: float, trace: bool, t_process: float,
        peaks: Optional[dict] = None, control: bool = False,
        fault=None, overrides: Optional[dict] = None,
        profiler_factory=None) -> Run:
    """One run of the backlog cell. ``control`` compares the reference
    computed through float32 lanes in the program's place; ``fault``
    (tests) breaks the timed path after the deployment is built;
    ``overrides`` (tests) shrink the traffic or the configuration."""
    from repro.configs.dod_etl import tpcdi_dimtrade_config  # noqa: F401
    from repro.core import DODETLPipeline, SourceDatabase
    from repro.runtime.cluster import ConcurrentCluster
    from bench import harness

    over = dict(overrides or {})
    c = dict(spec["config"], **over.pop("config", {}))
    tr = dict(spec["traffic"], **over)
    if tr["mode"] != "backlog":
        raise BenchError("tpcdi-dimtrade runs backlog traffic only")
    warmup = float(tr["warmup_s"])
    n = int(np.ceil(tr["fill_records_s"] * (warmup + seconds)))
    data = make_data(c, n, seed)
    if data.n_new_accounts > c["max_new_accounts"]:
        raise BenchError("the fill makes more new accounts than "
                         "max_new_accounts allows")
    a_slots, s_slots = cache_slots(c, data)
    cfg = etl_config(c, a_slots, s_slots)
    src = SourceDatabase()
    tracer = harness.annotated_tracer() if trace else None
    pipe = DODETLPipeline(cfg, src, n_workers=c["n_workers"], tracer=tracer)
    if pipe.backend.name != c["backend"]:
        raise BenchError(f"backend {pipe.backend.name}, configuration "
                         f"states {c['backend']}")
    cluster = ConcurrentCluster(pipe, poll_cdc=False)
    h = data.history
    zeros = lambda k: np.zeros(k, np.int64)
    pipe.bootstrap_caches({
        "DimAccount": (data.accounts[:h, 1], data.accounts[:h],
                       zeros(h)),
        "DimSecurity": (data.securities[:, 1], data.securities,
                        zeros(len(data.securities)))})
    src.log.append(data.log)
    pipe.extract()
    warm_up(pipe)
    sizing = {"cache_slots": {"DimAccount": a_slots, "DimSecurity": s_slots},
              "cache_device_bytes": sum(
                  a.nbytes for w in pipe.workers for cache in w.caches
                  for a in cache.device_state()),
              "account_versions_setup": sum(w.caches[0].n_rows
                                            for w in pipe.workers),
              "records": len(data.log), "trade_rows": data.n_trade_rows,
              "new_accounts": data.n_new_accounts}
    initial_slots = [tuple(cache.n_slots for cache in w.caches)
                     for w in pipe.workers]
    if fault is not None:
        fault(pipe)

    transform_calls: List[tuple] = []
    if trace:
        be = pipe.backend
        inner = be.transform_block

        def recorded(payload, *a, **k):
            transform_calls.append((time.perf_counter(), len(payload)))
            return inner(payload, *a, **k)
        be.transform_block = recorded

    prof = profiler_factory(STAGE_SPANS) if trace else None
    cluster.start()
    t0 = time.perf_counter() + 0.05
    t_open, t_close = t0 + warmup, t0 + warmup + seconds

    def check():
        for rt in cluster.runtimes.values():
            if rt.error is not None:
                raise rt.error

    try:
        if prof is not None:
            sleep_until(t_open - 1.0, check)
            prof.start()
        sleep_until(t_open, check)
        loaded_open = pipe.warehouse.rows_loaded
        t_open = time.perf_counter()
        if prof is not None:
            with prof.window():
                sleep_until(t_close, check)
                t_close = time.perf_counter()
        else:
            sleep_until(t_close, check)
            t_close = time.perf_counter()
        loaded_close = pipe.warehouse.rows_loaded
        # the broker lag at the close; nothing after the window needs
        # loading, so the stage threads stop here (stop_all joins them)
        backlog_left = int(cluster._operational_lag())
        for rt in cluster.runtimes.values():
            rt.stop.set()
        device_trace = prof.stop_and_reduce() if prof is not None else None
        import jax
        memory_peak = harness.device_info(jax.devices(),
                                          1)["memory_peak_bytes"]
    finally:
        cluster.stop_all()
        if trace:
            del pipe.backend.transform_block
    spans = list(tracer.events()) if tracer is not None else []

    notes: Dict[str, object] = dict(
        sizing, backlog_left=backlog_left, memory_peak_bytes=memory_peak,
        held_at_close=sum(len(w.buffer) for w in pipe.workers),
        asof_overflows=sum(w.caches[0].overflows for w in pipe.workers),
        horizon_lifts=pipe.metrics.counters().get("worker.horizon_lifts", 0))
    window_len = t_close - t_open
    e2e = {"setup_s": t_open - t_process,
           "loaded_records_s": (loaded_close - loaded_open) / window_len}
    compared = compare(c, data, pipe, initial_slots, control)
    if control:     # the sound readings of the same run, for the limits
        notes["compared_sound"] = compare(c, data, pipe, initial_slots,
                                          False)
    return Run(config=c, window=(t_open, t_close), e2e=e2e,
               attempted=loaded_close - loaded_open, failed=0,
               compared=compared, notes=notes, spans=spans,
               transform_calls=transform_calls, device_trace=device_trace,
               peaks=peaks)


# ----------------------------------------------------------------- compare
def compare(c: dict, data: Data, pipe, initial_slots,
            control: bool) -> Dict[str, float]:
    """The numbers that decide ``correct``, against the plain reference:

    * ``bad_records``: CDC records not applied exactly once: the records
      the committed offsets cover must each be applied once or be held in
      a late buffer at the close; a held record's later records of its
      TradeID must be held too (an update never overtakes its insert); the
      count of CDC records applied to each DimTrade row must be the
      reference's.
    * ``dimtrade_rows_off``: DimTrade rows missing, extra, or differing in
      any field from the reference's final DimTrade over the applied
      records, in log order.
    * ``caches_grown``: caches whose slot count changed after set-up.

    With ``control`` the reference computed through float32 lanes stands
    in for the program's rows; which records each holds stays the
    program's."""
    topic = pipe.queue.topics[pipe.operational_topics[0]]
    group = {w.name: w.group for w in pipe.workers}
    lsns, committed = [], 0
    for p, owner in pipe.assignment.assignment.items():
        k = pipe.queue.committed(group[owner], pipe.operational_topics[0], p)
        committed += k
        if k:
            lsns.append(np.asarray(topic.partitions[p].read(0, k).lsn))
    handled = np.sort(np.concatenate(lsns)) if lsns else np.zeros(0, int)
    held = np.sort(np.concatenate(
        [w.buffer.peek().lsn for w in pipe.workers]
        + [w.dead_letter.peek().lsn for w in pipe.workers]))
    bad = abs(len(np.unique(handled)) - committed)
    bad += int((~np.isin(held, handled)).sum())
    applied = np.setdiff1d(handled, held)
    log = data.log
    rows_all, found = reference.dimtrade_rows(
        log.payload[applied], data.accounts, data.securities)
    bad += int((~found).sum())           # applied without its dimensions
    tid = log.row_key
    # an applied record behind a held earlier record of its TradeID
    held_first = {}
    for lsn in held:
        held_first.setdefault(int(tid[lsn]), int(lsn))
    if held_first:
        hk = np.fromiter(held_first.keys(), np.int64)
        hf = np.fromiter(held_first.values(), np.int64)
        o = np.argsort(hk)
        hk, hf = hk[o], hf[o]
        pos = np.minimum(np.searchsorted(hk, tid[applied]), len(hk) - 1)
        bad += int(((hk[pos] == tid[applied]) & (hf[pos] < applied)).sum())
    want, want_n = reference.apply(rows_all)
    if control:
        cand_rows, _ = reference.dimtrade_rows(
            log.payload[applied], data.accounts, data.securities,
            dtype=np.float32)
        got, got_n = reference.apply(cand_rows)
    else:
        got, got_n = pipe.warehouse.keyed.table()
        o = np.argsort(got[:, 0], kind="stable")
        got, got_n = got[o].astype(np.int64), got_n[o].astype(np.int64)
    g_ids, w_ids = got[:, 0], want[:, 0]
    common, gi, wi = np.intersect1d(g_ids, w_ids, return_indices=True)
    rows_off = (len(g_ids) - len(common)) + (len(w_ids) - len(common))
    rows_off += int((got[gi] != want[wi]).any(axis=1).sum())
    bad += int(np.abs(got_n[gi] - want_n[wi]).sum())
    bad += int(got_n.sum() - got_n[gi].sum())
    return {"bad_records": float(bad),
            "dimtrade_rows_off": float(rows_off),
            "caches_grown": float(sum(
                tuple(cache.n_slots for cache in w.caches) != s
                for w, s in zip(pipe.workers, initial_slots)))}
