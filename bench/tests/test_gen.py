"""Every seed gets the same work: the same records per unit and the same
late count, in another order."""
import numpy as np

from bench import gen


def test_same_work_any_seed():
    a = gen.make_records(1000, 20, 0.05, seed=1)
    b = gen.make_records(1000, 20, 0.05, seed=2 ** 31 + 5)
    assert np.array_equal(np.bincount(a.unit), np.bincount(b.unit))
    assert a.late.sum() == b.late.sum() == 50
    assert not np.array_equal(a.unit, b.unit)
    c = gen.make_records(1000, 20, 0.05, seed=1)
    assert np.array_equal(a.qty, c.qty)


def test_stream_schedule():
    from repro.configs.dod_etl import steelworks_config
    rec = gen.make_records(1000, 20, 0.05, seed=3)
    tables = gen.Tables([t.name for t in steelworks_config().tables])
    s = gen.Stream(rec, tables, rate=2000, tick_s=0.01, n_prod_ticks=40,
                   late_delay_ticks=(1, 20), seed=3)
    assert s.n_records == 800
    prod = sum(int((b.table_id == tables.production).sum())
               for b in s.batches)
    qual = sum(int((b.table_id == tables.quality).sum()) for b in s.batches)
    assert prod == qual == 800
    assert len(s.batches) <= 40 + 20


def test_master_history():
    """The history's inspections name products no record of the run has,
    spread evenly over the units, all before the run's first transaction."""
    from repro.configs.dod_etl import steelworks_config
    rec = gen.make_records(500, 20, 0.05, seed=4)
    tables = gen.Tables([t.name for t in steelworks_config().tables])
    eq, qu = gen.history(len(rec), 1000, 20, rec.status, tables, seed=4)
    pid = qu.payload[:, 3].astype(np.int64)
    assert pid.min() == 500 and len(np.unique(pid)) == 1000
    assert np.array_equal(np.bincount(qu.business_key), np.full(20, 50))
    assert (qu.txn_time < rec.txn.min()).all()
    assert np.array_equal(eq.payload[:, 5], rec.status)
