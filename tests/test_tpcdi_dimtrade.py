"""The TPC-DI DimTrade deployment on the CPU at small sizes, against the
plain reference (``dimtrade_reference.py``, which imports nothing of the
program): the versioned cache's point-in-time probe, int32 lanes past
2**24, the keyed upsert, ``ConcurrentCluster`` end to end (exactly once, a
late account version, an update held behind its insert) and a replicated
DimSecurity across rebalances."""
import sys
from pathlib import Path

import numpy as np
import pytest

import dimtrade_reference as reference
from repro.configs.dod_etl import tpcdi_dimtrade_config
from repro.core import DODETLPipeline, SourceDatabase
from repro.core import dimtrade as dt
from repro.core.backend import JaxBackend, NumpyBackend
from repro.core.cache import MAX_PROBES, InMemoryTable, VersionedTable
from repro.core.loader import KeyedTable
from repro.core.records import OP_INSERT, OP_UPDATE, make_batch
from repro.runtime.cluster import ConcurrentCluster

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))
from bench import tpcdi  # noqa: E402  (the benchmark's data generator)

BIG = (1 << 24) + 1          # the first integer float32 cannot hold
EFF = dt.ACCOUNT_COLUMNS.index("effective_date")
AID = dt.ACCOUNT_COLUMNS.index("account_id")
SMALL = {"n_accounts": 3000, "history_second_version_frac": 0.2,
         "max_new_accounts": 1000, "n_securities": 300, "n_companies": 50,
         "n_brokers": 40, "n_customers": 2000, "n_exec_names": 100,
         "t0": 1499074200, "source_records_s": 2000,
         "account_cdc_frac": 0.02, "new_account_share": 0.5,
         "late_account_delay_ms": [10, 200], "update_delay_ms": [0, 2000],
         "cancel_frac": 0.05}


@pytest.fixture
def copied_cache_uploads(monkeypatch):
    """XLA:CPU may alias an aligned numpy array instead of copying it, so a
    cache's device mirror could change under an in-flight transform (a
    TPU copies host to device). Upload copies, as on the chip."""
    import jax.numpy as jnp
    from repro.core import cache

    def device_state(self):
        if self._device is None or self._dirty:
            self._device = tuple(jnp.asarray(np.array(a, copy=True))
                                 for a in (self.keys, self.values, self.txn))
            self._dirty.clear()
        return self._device

    monkeypatch.setattr(cache.InMemoryTable, "device_state", device_state)


def version_rows(keys, effs, tag=0):
    """DimAccount rows for (account, EffectiveDate) pairs; the surrogate key
    names the version."""
    n = len(keys)
    rows = np.zeros((n, 8), np.int32)
    rows[:, 0] = BIG + tag + np.arange(n)
    rows[:, AID] = keys
    rows[:, 2] = BIG + 7
    rows[:, EFF] = effs
    return rows


def scan(rows, q, t):
    """Brute force: the latest version of ``q`` in effect at ``t``."""
    mine = rows[(rows[:, AID] == q) & (rows[:, EFF] <= t)]
    return None if not len(mine) else mine[np.argmax(mine[:, EFF])]


def probe_both(table, q, t):
    """The point-in-time probe through the jax kernel's probe and the
    numpy backend's twin; both must agree."""
    import jax.numpy as jnp
    from repro.core.backend import _lookup_asof_np
    from repro.core.cache import lookup_asof
    jv, jf = lookup_asof(jnp.asarray(q, jnp.int32), jnp.asarray(t, jnp.int32),
                         jnp.asarray(table.keys), jnp.asarray(table.values),
                         table.asof_lane)
    nv, nf = _lookup_asof_np(q, t, table.keys, table.values, table.asof_lane)
    np.testing.assert_array_equal(np.asarray(jf), nf)
    np.testing.assert_array_equal(np.asarray(jv), nv)
    return nv, nf


# ---------------------------------------------------------- versioned cache
def test_point_in_time_probe_matches_a_scan_over_versions():
    rng = np.random.default_rng(5)
    keys = np.r_[np.repeat(np.arange(200), 3), np.full(12, 999)]
    effs = np.r_[rng.integers(1_000, 50_000, 600),
                 1_000 * (1 + np.arange(12))]
    rows = version_rows(keys, effs)
    table = VersionedTable(4096, 8, EFF)
    table.upsert(rows[:, AID], rows, np.ones(len(rows)))
    assert table.n_rows == len(rows) and table.overflows == 0
    # times equal to an EffectiveDate, between versions, before the first
    q = np.r_[keys, keys, np.full(14, 999)]
    t = np.r_[effs, effs - 1, 1_000 * np.arange(14) + 500]
    vals, found = probe_both(table, q, t)
    for i in range(len(q)):
        want = scan(rows, q[i], t[i])
        assert found[i] == (want is not None)
        if want is not None:
            np.testing.assert_array_equal(vals[i], want)


def test_version_overwrite_is_last_writer_by_txn():
    table = VersionedTable(256, 8, EFF)
    rows = version_rows([7, 7], [100, 100])
    table.upsert(rows[:, AID], rows, np.array([5, 3]))
    assert table.n_rows == 1
    vals, found = probe_both(table, [7], [100])
    assert found[0] and vals[0, 0] == rows[0, 0]


def test_overflow_is_counted_and_never_a_wrong_version():
    """A key with more versions than its probe window holds: each extra
    version is an overflow, the oldest is dropped, and a probe in a
    dropped version's interval finds nothing (never an older or newer
    version)."""
    n = MAX_PROBES + 3
    effs = 100 * (1 + np.arange(n))
    rows = version_rows(np.full(n, 42), effs)
    table = VersionedTable(1024, 8, EFF)
    table.upsert(rows[:, AID], rows, np.ones(n))
    assert table.overflows == 3
    assert table.n_rows == MAX_PROBES
    vals, found = probe_both(table, np.full(n, 42), effs + 50)
    assert not found[:3].any()
    assert found[3:].all()
    np.testing.assert_array_equal(vals[3:], rows[3:])


def test_crowded_window_grows_instead_of_overflowing():
    table = VersionedTable(16, 8, EFF)
    keys = np.arange(40)
    rows = version_rows(keys, np.full(40, 10))
    table.upsert(keys, rows, np.ones(40))
    assert table.overflows == 0 and table.n_slots > 16
    vals, found = probe_both(table, keys, np.full(40, 10))
    assert found.all()
    np.testing.assert_array_equal(vals, rows)


# -------------------------------------------------------------- int32 lanes
@pytest.mark.parametrize("backend", [NumpyBackend, JaxBackend],
                         ids=["numpy", "jax"])
def test_int32_lanes_round_trip_past_float32(backend):
    be = backend()
    table = InMemoryTable(512, 8, dtype=np.int32)
    keys = np.arange(100)
    vals = (BIG + np.arange(800)).reshape(100, 8).astype(np.int32)
    table.upsert(keys, vals, np.ones(100))
    state = (table.device_state() if be.device
             else (table.keys, table.values, table.txn))
    got, found, _ = be.hash_probe(keys, *state)
    assert found.all()
    assert np.asarray(got).dtype == np.int32
    np.testing.assert_array_equal(got, vals)


@pytest.mark.parametrize("backend", [NumpyBackend, JaxBackend],
                         ids=["numpy", "jax"])
def test_dimtrade_transform_matches_reference(backend):
    data = tpcdi.make_data(SMALL, 4000, 11)
    acct = VersionedTable(16384, 8, EFF)
    acct.upsert(data.accounts[:, AID], data.accounts,
                np.zeros(len(data.accounts)))
    sec = InMemoryTable(1024, 8, dtype=np.int32)
    sec.upsert(data.securities[:, 1], data.securities,
               np.zeros(len(data.securities)))
    trade = data.log.payload[data.log.table_id == tpcdi.TRADE]
    rows, found = backend().transform(trade, acct, sec,
                                      schema="tpcdi-dimtrade")
    want, wfound = reference.dimtrade_rows(trade, data.accounts,
                                           data.securities)
    np.testing.assert_array_equal(found, wfound)
    assert found.all()
    assert np.asarray(rows).dtype == np.int32
    np.testing.assert_array_equal(np.asarray(rows, np.int64), want)
    past = {reference.OUT_COLS[c]
            for c in np.flatnonzero((want > (1 << 24)).any(axis=0))}
    assert {"trade_id", "sk_create_date_id", "sk_close_date_id",
            "sk_broker_id", "sk_company_id", "sk_customer_id"} <= past


def test_date_keys_match_the_calendar():
    t = np.array([0, 951782400, 951868799, 1499074200, 1709251199,
                  2147483647], np.int64)   # leap days, the int32 edge
    want = reference.date_time_keys(t)
    got = dt._civil(t, np)
    np.testing.assert_array_equal(got[0], want[0])
    np.testing.assert_array_equal(got[1], want[1])


# ------------------------------------------------------------ keyed upsert
def test_keyed_upsert_keeps_null_columns_and_counts_records():
    table = KeyedTable(key_col=0, keep=(2, 3))
    a = np.array([[BIG, 1, 10, -1, 5], [BIG + 1, 1, 11, -1, 5]], np.int32)
    b = np.array([[BIG, 2, -1, 20, 6], [BIG, 3, -1, -1, 7],
                  [BIG + 2, 1, -1, -1, 1]], np.int32)
    assert table.upsert(a) == 2
    assert table.upsert(b) == 1
    rows, applied = table.table()
    assert rows.tolist() == [[BIG, 3, 10, 20, 7], [BIG + 1, 1, 11, -1, 5],
                             [BIG + 2, 1, -1, -1, 1]]
    assert applied.tolist() == [3, 1, 1]
    # the index survives growth
    big = np.zeros((5000, 5), np.int32)
    big[:, 0] = 1000 + np.arange(5000)
    table.upsert(big)
    table.upsert(big)
    rows, applied = table.table()
    assert len(rows) == 5003 and applied[3:].tolist() == [2] * 5000


# --------------------------------------------------------- end to end
def build(n_partitions=4, n_workers=2, slots=16384):
    cfg = tpcdi_dimtrade_config(n_accounts=SMALL["n_accounts"]
                                + SMALL["max_new_accounts"],
                                n_partitions=n_partitions, backend="jax")
    import dataclasses
    cfg = dataclasses.replace(cfg, cache_slots=slots)
    src = SourceDatabase()
    pipe = DODETLPipeline(cfg, src, n_workers=n_workers)
    return cfg, src, pipe


def final_table(pipe):
    rows, applied = pipe.warehouse.keyed.table()
    o = np.argsort(rows[:, 0])
    return rows[o].astype(np.int64), applied[o].astype(np.int64)


def test_concurrent_cluster_matches_reference(copied_cache_uploads):
    """The whole log through ``ConcurrentCluster``: the final DimTrade
    equals the reference's, every CDC record applied exactly once, and
    late account versions held trades in the late buffer on the way."""
    data = tpcdi.make_data(SMALL, 6000, 3221225473)
    cfg, src, pipe = build()
    h = data.history
    pipe.bootstrap_caches({
        "DimAccount": (data.accounts[:h, AID], data.accounts[:h],
                       np.zeros(h)),
        "DimSecurity": (data.securities[:, 1], data.securities,
                        np.zeros(len(data.securities)))})
    src.log.append(data.log)
    pipe.extract()
    cluster = ConcurrentCluster(pipe, max_records_per_partition=64,
                                poll_cdc=False)
    cluster.start()
    try:
        cluster.run_until_idle(timeout=120)
    finally:
        cluster.stop_all()
    assert sum(len(w.buffer) for w in pipe.workers) == 0
    trade = data.log.payload[data.log.table_id == tpcdi.TRADE]
    want, want_n = reference.apply(reference.dimtrade_rows(
        trade, data.accounts, data.securities)[0])
    got, got_n = final_table(pipe)
    np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(got_n, want_n)
    assert pipe.warehouse.rows_loaded == len(trade)
    assert pipe.metrics.counters()["worker.records_held"] > 0


def test_update_never_overtakes_its_held_insert(copied_cache_uploads):
    """One record fetched per step, in log order. Trade B: its insert
    precedes its account's only version (never joinable, so it waits), and
    its update, joinable, waits behind it for good. Trade A, of a new
    account whose version lands between its insert and its update: held
    or not (that depends on how far ingest ran ahead of the transform),
    both apply in log order, so the stored row keeps the insert's create
    keys and carries the update's status."""
    t0 = SMALL["t0"]
    acct_a = version_rows([5], [t0], tag=100)
    acct_b = version_rows([7], [t0 + 5], tag=200)
    sec = np.zeros((1, 8), np.int32)
    sec[0, :2] = (BIG + 3, 9)

    def trade(tid, ca, flag, dts, st):
        row = np.zeros(16, np.int32)
        row[[0, 2, 3, 4, 5, 7, 8, 10]] = (flag, tid, dts, st, dt.TT["TMB"],
                                          9, 100, ca)
        return row

    a_ins = trade(BIG + 50, 5, dt.CDC_INSERT, t0, dt.ST["SBMT"])
    a_upd = trade(BIG + 50, 5, dt.CDC_UPDATE, t0 + 1, dt.ST["CMPT"])
    b_ins = trade(BIG + 60, 7, dt.CDC_INSERT, t0, dt.ST["SBMT"])
    b_upd = trade(BIG + 60, 7, dt.CDC_UPDATE, t0 + 6, dt.ST["CMPT"])
    log = [(0, OP_INSERT, a_ins, 0), (1, OP_INSERT, acct_b[0], 10),
           (0, OP_INSERT, b_ins, 20), (0, OP_UPDATE, b_upd, 30),
           (1, OP_INSERT, acct_a[0], 100), (0, OP_UPDATE, a_upd, 150)]
    cfg, src, pipe = build(n_partitions=1, n_workers=1)
    pipe.bootstrap_caches({"DimAccount": (acct_a[:0, AID], acct_a[:0],
                                          np.zeros(0)),
                           "DimSecurity": (sec[:, 1], sec, np.zeros(1))})
    for table_id, op, row, txn in log:
        payload = np.zeros((1, 16), np.int32)
        payload[0, :len(row)] = row
        key = (row[2], row[10]) if table_id == 0 else (row[0], row[AID])
        src.log.append(make_batch(table_id, op, [key[0]], [key[1]], [txn],
                                  payload))
    pipe.extract()
    cluster = ConcurrentCluster(pipe, max_records_per_partition=1,
                                poll_cdc=False)
    cluster.start()
    try:
        cluster.run_until_idle(timeout=60, stall_s=1.0)
    finally:
        cluster.stop_all()
    assert pipe.metrics.counters()["worker.records_held"] >= 2
    held = pipe.workers[0].buffer.peek()
    assert held.row_key.tolist() == [BIG + 60, BIG + 60]
    np.testing.assert_array_equal(held.payload, np.stack([b_ins, b_upd]))
    got, applied = final_table(pipe)
    want, want_n = reference.apply(reference.dimtrade_rows(
        np.stack([a_ins, a_upd]), np.r_[acct_a, acct_b], sec)[0])
    np.testing.assert_array_equal(got, want)
    assert applied.tolist() == want_n.tolist() == [2]
    c = reference.OUT_COLS.index
    assert got[0, c("sk_create_date_id")] == 20170703
    assert got[0, c("status")] == dt.STATUS_NAME[dt.ST["CMPT"]]
    assert got[0, c("sk_account_id")] == acct_a[0, 0]


# ------------------------------------------------------ replicated scope
def publish_dimensions(src, accounts, securities):
    for table_id, rows, bkey in ((1, accounts, accounts[:, AID]),
                                 (2, securities, securities[:, 1])):
        payload = np.zeros((len(rows), 16), np.int32)
        payload[:, :8] = rows
        src.log.append(make_batch(table_id, OP_INSERT, rows[:, 0], bkey,
                                  np.zeros(len(rows)), payload))


def check_scopes(pipe, accounts, n_securities, exact=True):
    """Every worker holds the whole DimSecurity and every version of its
    own accounts; ``exact``: no other account's (the concurrent runtime
    drops a revoked range's rows lazily, at the worker's next grant)."""
    for w in pipe.workers:
        acct, sec = w.caches
        assert sec.n_rows == n_securities
        live = acct.values[acct.keys != -1]
        mine = w.assigned_business_keys(pipe.cfg.n_business_keys)
        own = np.isin(live[:, AID], mine)
        assert own.sum() == np.isin(accounts[:, AID], mine).sum()
        assert own.all() or not exact


def test_held_records_filling_the_late_buffer_do_not_stall(
        copied_cache_uploads):
    """A new account's version lands more records after its first trades
    than the late buffer holds: its held trades fill the buffer, so ingest
    cannot fetch up to the version, and the log-order pump goes past the
    newest record fetched to reach it (counted) instead of waiting for
    good."""
    import dataclasses
    t0, n, late_at = SMALL["t0"], 80, 100
    acct = version_rows([5], [t0], tag=100)
    sec = np.zeros((1, 8), np.int32)
    sec[0, :2] = (BIG + 3, 9)
    trades = np.zeros((n, 16), np.int32)
    trades[:, [0, 3, 4, 5, 7, 8, 10]] = (dt.CDC_INSERT, t0, dt.ST["SBMT"],
                                         dt.TT["TMB"], 9, 100, 5)
    trades[:, 2] = BIG + np.arange(n)
    cfg, src, pipe = build(n_partitions=1, n_workers=1)
    cfg = dataclasses.replace(cfg, buffer_capacity=32)
    pipe = DODETLPipeline(cfg, src, n_workers=1)
    pipe.bootstrap_caches({"DimAccount": (acct[:0, AID], acct[:0],
                                          np.zeros(0)),
                           "DimSecurity": (sec[:, 1], sec, np.zeros(1))})
    src.log.append(make_batch(0, OP_INSERT, trades[:, 2], trades[:, 10],
                              np.arange(n), trades))
    payload = np.zeros((1, 16), np.int32)
    payload[0, :8] = acct[0]
    src.log.append(make_batch(1, OP_INSERT, [acct[0, 0]], [acct[0, AID]],
                              [late_at], payload))
    pipe.extract()
    cluster = ConcurrentCluster(pipe, max_records_per_partition=8,
                                poll_cdc=False)
    cluster.start()
    try:
        cluster.run_until_idle(timeout=60, stall_s=2.0)
    finally:
        cluster.stop_all()
    assert len(pipe.workers[0].buffer) == 0
    got, applied = final_table(pipe)
    want, want_n = reference.apply(reference.dimtrade_rows(
        trades, acct, sec)[0])
    np.testing.assert_array_equal(got, want)
    assert applied.tolist() == want_n.tolist() == [1] * n
    counters = pipe.metrics.counters()
    assert counters["worker.records_held"] >= cfg.buffer_capacity
    assert counters["worker.horizon_lifts"] >= 1


@pytest.mark.parametrize("runtime", ["sequential", "concurrent"])
def test_replicated_security_stays_whole_across_rebalance(runtime):
    data = tpcdi.make_data(SMALL, 2000, 99)
    accounts = data.accounts[:data.history]
    cfg, src, pipe = build(n_partitions=8, n_workers=2)
    publish_dimensions(src, accounts, data.securities)
    pipe.extract()
    pipe.bootstrap_caches()
    check_scopes(pipe, accounts, len(data.securities))
    if runtime == "sequential":
        pipe.add_workers(2)
        check_scopes(pipe, accounts, len(data.securities))
        pipe.fail_workers(["w0"])
    else:
        cluster = ConcurrentCluster(pipe, poll_cdc=False)
        cluster.start()
        try:
            cluster.scale_to(4)
            check_scopes(pipe, accounts, len(data.securities), exact=False)
            cluster.fail_workers(["w1"])
        finally:
            cluster.stop_all()
    check_scopes(pipe, accounts, len(data.securities),
                 exact=runtime == "sequential")


def test_journal_restores_dimtrade(tmp_path):
    """A checkpoint mid-stream, a cold restart from the journal, the rest
    of the stream: DimTrade equals the reference's over the whole log,
    every CDC record applied once (the journal holds the keyed target
    whole; offsets resume after the checkpoint)."""
    from repro.durability import (DurabilityJournal, RecoveryCoordinator,
                                  recover_pipeline)
    data = tpcdi.make_data(SMALL, 1500, 7)
    accounts = data.accounts[:data.history]
    cfg, src, pipe = build(n_partitions=4, n_workers=2)
    publish_dimensions(src, accounts, data.securities)
    src.log.append(data.log)
    pipe.extract()
    pipe.bootstrap_caches()
    journal = DurabilityJournal(str(tmp_path / "j"))
    coord = RecoveryCoordinator(journal)
    for _ in range(3):
        pipe.step(64)
    coord.checkpoint(pipe)
    assert 0 < pipe.warehouse.rows_loaded < len(data.log)
    pipe2, _, info = recover_pipeline(cfg, src, journal, n_workers=2)
    assert info is not None
    np.testing.assert_array_equal(pipe2.warehouse.keyed.table()[0],
                                  pipe.warehouse.keyed.table()[0])
    pipe2.run_to_completion()
    trade = data.log.payload[data.log.table_id == tpcdi.TRADE]
    want, want_n = reference.apply(reference.dimtrade_rows(
        trade, data.accounts, data.securities)[0])
    got, got_n = final_table(pipe2)
    np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(got_n, want_n)
