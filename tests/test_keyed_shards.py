"""The keyed target split into one shard per load stage: a block's rows land
in the shard of their static partition's first owner, in log order per key,
whoever writes them; the shards together read as one ``KeyedTable``;
concurrent owners need no shared lock; a partition moved by a rebalance
keeps its rows where they are; the load counters stay exact; and a journal
restores the shards and their map into a pipeline of another worker
count."""
import sys
import threading
from pathlib import Path

import numpy as np
import pytest

import dimtrade_reference as reference
from repro.configs.dod_etl import tpcdi_dimtrade_config
from repro.core import DODETLPipeline, SourceDatabase
from repro.core.loader import KeyedTable, StarSchemaWarehouse
from repro.core.partitioning import partition_of
from repro.core.records import OP_INSERT, make_batch
from repro.observability import StageTracer
from repro.runtime.cluster import ConcurrentCluster

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))
from bench import tpcdi  # noqa: E402  (the benchmark's data generator)

N_PARTS, OWNERS = 20, [f"w{i}" for i in range(5)]
WIDTH, KEEP = 6, (2, 3)
AID = 1                      # DimAccount's account_id column
SMALL = {"n_accounts": 3000, "history_second_version_frac": 0.2,
         "max_new_accounts": 1000, "n_securities": 300, "n_companies": 50,
         "n_brokers": 40, "n_customers": 2000, "n_exec_names": 100,
         "t0": 1499074200, "source_records_s": 2000,
         "account_cdc_frac": 0.02, "new_account_share": 0.5,
         "late_account_delay_ms": [10, 200], "update_delay_ms": [0, 2000],
         "cancel_frac": 0.05}


def cdc_stream(seed, n=3000, n_trades=1200, n_accounts=400):
    """(rows, business keys) in log order: each trade inserted once with
    its account, then updated; update rows leave the ``KEEP`` columns NULL
    (-1) at random."""
    rng = np.random.default_rng(seed)
    account = rng.integers(0, n_accounts, n_trades)
    first = np.arange(n_trades)
    rest = rng.integers(0, n_trades, n - n_trades)
    trades = np.concatenate([first, rest])
    trades = trades[np.argsort(np.r_[rng.random(n_trades) * n,
                                     n_trades + rng.random(n - n_trades) * n],
                               kind="stable")]
    # the first record of each trade is its insert
    _, first_at = np.unique(trades, return_index=True)
    rows = rng.integers(0, 1000, (n, WIDTH)).astype(np.int32)
    rows[:, 0] = 10_000 + trades
    null = rng.random((n, len(KEEP))) < 0.5
    null[first_at] = False
    rows[:, list(KEEP)] = np.where(null, -1, rows[:, list(KEEP)])
    return rows, account[trades].astype(np.int64)


def sharded():
    wh = StarSchemaWarehouse()
    owner_of = [OWNERS[p % len(OWNERS)] for p in range(N_PARTS)]
    wh.keyed_target(0, KEEP, OWNERS, owner_of)
    return wh, owner_of


def owner_blocks(rows, bkeys, owner_of, size=40):
    """Per owner, its rows (its static partitions') in log order, cut into
    blocks of ``size``: what each load stage upserts."""
    owner = np.asarray(owner_of)[partition_of(bkeys, N_PARTS)]
    out = {}
    for o in OWNERS:
        sel = np.flatnonzero(owner == o)
        out[o] = [np.column_stack([rows[sel[i:i + size]],
                                   bkeys[sel[i:i + size]]])
                  for i in range(0, len(sel), size)]
    return out


def sorted_table(rows, applied):
    o = np.argsort(rows[:, 0])
    return rows[o], applied[o]


def test_sharded_target_equals_one_keyed_table():
    rows, bkeys = cdc_stream(3221225901)
    one = KeyedTable(0, KEEP)
    one.upsert(rows)
    wh, owner_of = sharded()
    for o, blocks in owner_blocks(rows, bkeys, owner_of).items():
        for b in blocks:
            _, placed = wh.upsert(b)
            assert placed == {o: len(b)}
    want, want_n = sorted_table(*one.table())
    got, got_n = sorted_table(*wh.keyed.table())
    np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(got_n, want_n)
    assert got_n.sum() == len(rows)
    # every shard holds its own partitions' trades, and only those
    account_of = dict(zip(rows[:, 0].tolist(), bkeys.tolist()))
    for k, t in enumerate(wh.keyed.shards):
        accounts = [account_of[tid] for tid in t.table()[0][:, 0].tolist()]
        parts = partition_of(np.asarray(accounts, np.int64), N_PARTS)
        assert {owner_of[p] for p in parts.tolist()} == {OWNERS[k]}


def test_concurrent_writers_equal_a_serial_run():
    """More load stages than cores (ten, two to a shard, as after a
    rebalance), each upserting its own partitions at once with thread
    switches forced often: the same table as a serial run, and no load
    counted twice or lost."""
    rows, bkeys = cdc_stream(7, n=6000, n_trades=2000)
    part = partition_of(bkeys, N_PARTS)
    writers = [np.flatnonzero(part % 10 == j) for j in range(10)]
    blocks = [[np.column_stack([rows[sel[i:i + 25]], bkeys[sel[i:i + 25]]])
               for i in range(0, len(sel), 25)] for sel in writers]
    serial, _ = sharded()
    for mine in blocks:
        for b in mine:
            serial.upsert(b)
    wh, _ = sharded()
    start = threading.Barrier(len(blocks))
    errors = []

    def stage(mine):
        try:
            start.wait(timeout=30)
            for b in mine:
                wh.upsert(b)
        except Exception as e:        # surfaced below
            errors.append(e)

    threads = [threading.Thread(target=stage, args=(m,)) for m in blocks]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads) and not errors
    for got, want in zip(sorted_table(*wh.keyed.table()),
                         sorted_table(*serial.keyed.table())):
        np.testing.assert_array_equal(got, want)
    assert wh.rows_loaded == serial.rows_loaded == len(rows)
    assert wh.load_calls == serial.load_calls == sum(map(len, blocks))


def test_foreign_rows_take_their_shard_and_count():
    """Rows of a partition another worker owns go to that shard, in one
    call per shard, and count as loaded; every shard call counts."""
    rows, bkeys = cdc_stream(11)
    wh, owner_of = sharded()
    n = calls = 0
    for i in range(0, len(rows), 100):
        block = np.column_stack([rows[i:i + 100], bkeys[i:i + 100]])
        _, placed = wh.upsert(block)
        want = np.unique(np.asarray(owner_of)[partition_of(
            bkeys[i:i + 100], N_PARTS)], return_counts=True)
        assert placed == dict(zip(want[0].tolist(), want[1].tolist()))
        n += len(block)
        calls += len(placed)
    assert calls > len(rows) // 100       # blocks that spanned shards
    assert wh.rows_loaded == n == len(rows)
    assert wh.load_calls == calls
    one = KeyedTable(0, KEEP)
    one.upsert(rows)
    for got, want in zip(sorted_table(*wh.keyed.table()),
                         sorted_table(*one.table())):
        np.testing.assert_array_equal(got, want)


# --------------------------------------------------------- the deployment
@pytest.fixture
def copied_cache_uploads(monkeypatch):
    """Upload copies of the caches to the device, as a TPU does (XLA:CPU
    may alias an aligned numpy array)."""
    import jax.numpy as jnp
    from repro.core import cache

    def device_state(self):
        if self._device is None or self._dirty:
            self._device = tuple(jnp.asarray(np.array(a, copy=True))
                                 for a in (self.keys, self.values, self.txn))
            self._dirty.clear()
        return self._device

    monkeypatch.setattr(cache.InMemoryTable, "device_state", device_state)


def build(n_partitions, n_workers, tracer=None):
    cfg = tpcdi_dimtrade_config(n_accounts=SMALL["n_accounts"]
                                + SMALL["max_new_accounts"],
                                n_partitions=n_partitions, backend="jax")
    import dataclasses
    cfg = dataclasses.replace(cfg, cache_slots=16384)
    src = SourceDatabase()
    return cfg, src, DODETLPipeline(cfg, src, n_workers=n_workers,
                                    tracer=tracer)


def publish(src, data):
    accounts = data.accounts[:data.history]
    for table_id, rows, bkey in ((1, accounts, accounts[:, AID]),
                                 (2, data.securities, data.securities[:, 1])):
        payload = np.zeros((len(rows), 16), np.int32)
        payload[:, :8] = rows
        src.log.append(make_batch(table_id, OP_INSERT, rows[:, 0], bkey,
                                  np.zeros(len(rows)), payload))
    src.log.append(data.log)


def check_reference(pipe, data):
    trade = data.log.payload[data.log.table_id == tpcdi.TRADE]
    want, want_n = reference.apply(reference.dimtrade_rows(
        trade, data.accounts, data.securities)[0])
    rows, applied = pipe.warehouse.keyed.table()
    assert len(np.unique(rows[:, 0])) == len(rows)      # no duplicate key
    got, got_n = sorted_table(rows.astype(np.int64), applied.astype(np.int64))
    np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(got_n, want_n)
    assert pipe.warehouse.rows_loaded == len(trade) == got_n.sum()


def counted(tracer, name):
    return sum((args or {}).get("value", 0)
               for ph, nm, _lane, _t, _d, args in tracer.events()
               if ph == "C" and nm == name)


def test_moved_partition_updates_find_their_inserts():
    """A failed worker's partitions move to the survivors after their
    inserts: the survivors' updates of those trades land in the failed
    worker's shard (counted foreign), and DimTrade equals the
    reference's, each TradeID once."""
    data = tpcdi.make_data(SMALL, 2000, 3221225473)
    tracer = StageTracer()
    cfg, src, pipe = build(n_partitions=8, n_workers=4, tracer=tracer)
    publish(src, data)
    pipe.extract()
    pipe.bootstrap_caches()
    for _ in range(3):
        pipe.step(32)
    before = len(pipe.warehouse.keyed.shards[0].table()[0])
    assert before > 0 and counted(tracer, "load.upsert_foreign") == 0
    pipe.fail_workers(["w0"])
    pipe.run_to_completion()
    assert counted(tracer, "load.upsert_foreign") > 0
    assert pipe.warehouse.keyed.owners == ("w0", "w1", "w2", "w3")
    check_reference(pipe, data)


def test_rows_loaded_counts_retried_records(copied_cache_uploads):
    """Through ``ConcurrentCluster``, with records held for late account
    versions and loaded by retry sweeps: ``rows_loaded`` counts every
    upserted row once, and every lock wait is a span inside its upsert."""
    data = tpcdi.make_data(SMALL, 6000, 3221225473)
    tracer = StageTracer()
    cfg, src, pipe = build(n_partitions=20, n_workers=5, tracer=tracer)
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
    assert counted(tracer, "load.held") > 0
    check_reference(pipe, data)
    spans = [e for e in tracer.events() if e[0] == "X"]
    lock = sum(a["records"] for _p, n, *_r, a in spans
               if n == "load.upsert_lock")
    upsert = sum(a["records"] for _p, n, *_r, a in spans
                 if n == "load.upsert")
    assert lock == upsert == pipe.warehouse.rows_loaded
    assert counted(tracer, "load.upsert_foreign") == 0


def test_journal_restores_shards_into_fewer_workers(tmp_path):
    """A checkpoint after a failover (four shards, three live workers), a
    cold restart from the journal: ``table()`` reads the same rows in the
    same order, the shards and their map come back whole, and the rest of
    the stream completes DimTrade as the reference has it."""
    from repro.durability import (DurabilityJournal, RecoveryCoordinator,
                                  recover_pipeline)
    data = tpcdi.make_data(SMALL, 1500, 7)
    cfg, src, pipe = build(n_partitions=8, n_workers=4)
    publish(src, data)
    pipe.extract()
    pipe.bootstrap_caches()
    for _ in range(2):
        pipe.step(32)
    pipe.fail_workers(["w2"])
    pipe.step(32)
    journal = DurabilityJournal(str(tmp_path / "j"))
    RecoveryCoordinator(journal).checkpoint(pipe)
    pipe2, _, info = recover_pipeline(cfg, src, journal)
    assert info is not None and len(pipe2.workers) == 3
    for got, want in zip(pipe2.warehouse.keyed.table(),
                         pipe.warehouse.keyed.table()):
        np.testing.assert_array_equal(got, want)
    assert pipe2.warehouse.keyed.owners == pipe.warehouse.keyed.owners
    np.testing.assert_array_equal(pipe2.warehouse.keyed.shard_of_partition,
                                  pipe.warehouse.keyed.shard_of_partition)
    assert pipe2.warehouse.rows_loaded == pipe.warehouse.rows_loaded
    pipe2.run_to_completion()
    check_reference(pipe2, data)
