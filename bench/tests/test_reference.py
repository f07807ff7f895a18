"""The plain reference against the program at a tiny size: the program's
jitted transform (join + OEE) and its view fold agree with the numpy
reference to float32 rounding, and the bfloat16 control does not."""
import numpy as np
import pytest

from bench import gen, reference, steelworks

N, UNITS = 600, 20
VIEWS = {"n_shifts": 3, "shift_len": 4000.0, "n_windows": 32,
         "window_len": 2000.0}


@pytest.fixture(scope="module")
def program_facts():
    from repro.configs.dod_etl import steelworks_config
    from repro.core.cache import InMemoryTable
    from repro.core.backend import get_backend
    rec = gen.make_records(N, UNITS, 0.05, seed=2 ** 33 + 17)
    cfg = steelworks_config(n_partitions=UNITS, backend="jax")
    tables = gen.Tables([t.name for t in cfg.tables])
    idx = np.arange(N)
    eq, qu, prod = gen.batches(rec, idx, tables, False)
    equipment, quality = InMemoryTable(4096), InMemoryTable(4096)
    equipment.upsert(eq.payload[:, 1].astype(np.int64), eq.payload,
                     eq.txn_time)
    quality.upsert(qu.payload[:, 3].astype(np.int64), qu.payload,
                   qu.txn_time)
    be = get_backend("jax")
    block = be.transform_block(prod.payload, equipment, quality,
                               join_depth=3, n_units=UNITS)
    facts, found = block.to_host()
    assert found.all()
    return rec, facts, block.rollup_host()


def test_facts_match(program_facts):
    rec, facts, rollup = program_facts
    want = steelworks.ref_facts(rec, np.arange(N))
    assert np.array_equal(facts[:, [0, 1, 2, 9]], want[:, [0, 1, 2, 9]])
    assert reference.rel_err(facts[:, 3:9], want[:, 3:9]) < 1e-6
    assert reference.rel_err(rollup, reference.kpi_rollup(want, UNITS)) < 1e-5


def test_control_is_far(program_facts):
    rec, _, _ = program_facts
    want = steelworks.ref_facts(rec, np.arange(N))
    ctl = steelworks.ref_facts(rec, np.arange(N), steelworks.reference_dtype(True))
    assert reference.rel_err(ctl[:, 3:9], want[:, 3:9]) > 1e-3


def test_views_match(program_facts):
    from repro.serving import MaterializedViewEngine, steelworks_views
    rec, facts, _ = program_facts
    eng = MaterializedViewEngine(steelworks_views(UNITS), backend="jax")
    for lo in range(0, N, 128):       # deltas as the loads publish them
        eng.publish(facts[lo:lo + 128])
        eng.fold_pending()
    fold = reference.ViewFold(UNITS, VIEWS)
    fold.add(steelworks.ref_facts(rec, np.arange(N)))
    snap = eng.snapshot()
    for name, table in fold.tables.items():
        got = snap.view(name).table
        assert np.array_equal(got[:, 0], table[:, 0]), name
        assert reference.rel_err(got[:, 1:], table[:, 1:]) < 1e-5, name


def test_answers_match(program_facts):
    from repro.serving import (MaterializedViewEngine, ReportQuery,
                               ReportServer, steelworks_views)
    rec, facts, _ = program_facts
    eng = MaterializedViewEngine(steelworks_views(UNITS), backend="jax")
    eng.publish(facts)
    eng.fold_pending()
    fold = reference.ViewFold(UNITS, VIEWS)
    fold.add(steelworks.ref_facts(rec, np.arange(N)))
    mix = [("oee", 3), ("oee", -1), ("top_downtime", 5),
           ("production_rate", -1), ("production_curve", -1),
           ("shift_report", -1), ("kpi_rollup", -1), ("view", 1),
           ("view", 2)]
    qs = [ReportQuery(k, view=reference.VIEW_ARGS[a] if k == "view" else None,
                      unit=a if k == "oee" and a >= 0 else None,
                      k=a if k == "top_downtime" else 5) for k, a in mix]
    reps = ReportServer(eng).serve_batch(qs)
    for (kind, arg), rep in zip(mix, reps):
        want = reference.answer(kind, arg, fold.tables)
        have = steelworks.program_answer(kind, arg, rep, want)
        for k in have:
            assert reference.rel_err(have[k], want[k]) < 1e-5, (kind, k)


def test_rel_err_edges():
    assert reference.rel_err([np.inf, np.nan, 1.0], [np.inf, np.nan, 1.0]) == 0
    assert reference.rel_err([np.nan], [1.0]) == float("inf")
    assert reference.rel_err([2.0], [4.0]) == 0.5
    assert reference.rel_err([0.5], [0.25]) == 0.25
