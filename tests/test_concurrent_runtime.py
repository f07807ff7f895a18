"""Concurrent runtime tests (paper §3.1 "distributed, parallel" + §4.1.3).

The numpy backend keeps these fast and jit-free; the runtime under test is
identical for every backend (the backend only changes the numeric core).
"""
import dataclasses
import threading
import time

import numpy as np
import pytest

from repro.configs.dod_etl import steelworks_config
from repro.core import DODETLPipeline, SourceDatabase
from repro.core.message_queue import MessageQueue, TopicConfig
from repro.core.records import make_batch
from repro.data.sampler import SamplerConfig, SteelworksSampler
from repro.runtime.cluster import ConcurrentCluster


def build(n_workers, n_records=3000, n_partitions=8, late_frac=0.05,
          buffer_capacity=1024):
    cfg = steelworks_config(n_partitions=n_partitions, backend="numpy")
    cfg = dataclasses.replace(cfg, buffer_capacity=buffer_capacity)
    src = SourceDatabase()
    sampler = SteelworksSampler(cfg, SamplerConfig(
        records_per_table=n_records, n_equipment=n_partitions,
        late_master_frac=late_frac))
    pipe = DODETLPipeline(cfg, src, n_workers=n_workers)
    return cfg, src, sampler, pipe


def sequential_oracle(n_records, n_partitions=8, late_frac=0.05):
    _, src, sampler, pipe = build(1, n_records, n_partitions, late_frac)
    sampler.generate(src)
    pipe.extract()
    pipe.bootstrap_caches()
    pipe.run_to_completion()
    return pipe


def test_concurrent_byte_identical_to_sequential():
    """N concurrent workers produce a warehouse byte-identical to the
    single-worker sequential pipeline (pre-extracted stream, so both runs
    join every record against the same master versions)."""
    n = 3000
    _, src, sampler, pipe = build(4, n)
    sampler.generate(src)
    pipe.extract()                      # everything queued before start
    cluster = ConcurrentCluster(pipe, poll_cdc=False)
    cluster.start()
    done = cluster.run_until_idle(timeout=60)
    cluster.stop_all()
    assert done == n
    assert pipe.warehouse.rows_loaded == n

    oracle = sequential_oracle(n)
    a = pipe.warehouse.canonical_fact_table()
    b = oracle.warehouse.canonical_fact_table()
    assert a.shape == b.shape
    assert a.tobytes() == b.tobytes()   # literally byte-identical


def test_failover_under_load_loses_no_records():
    """§4.1.3 drill, for real: kill 2 of 5 workers while the feeder is
    still writing and the cluster is mid-stream; then scale back up. Zero
    records lost, zero duplicated, zero buffer drops."""
    n = 6000
    _, src, sampler, pipe = build(5, n, n_partitions=10,
                                  buffer_capacity=8192)
    feeder = threading.Thread(target=lambda: sampler.generate(src))
    cluster = ConcurrentCluster(pipe)
    cluster.start()
    feeder.start()
    time.sleep(0.15)                     # mid-run, under load
    redump = cluster.fail_workers(["w1", "w3"])
    assert redump >= 0.0
    assert sorted(cluster.alive_workers()) == ["w0", "w2", "w4"]
    time.sleep(0.1)
    cluster.scale_to(4)                  # elastic recovery, still streaming
    feeder.join()
    done = cluster.run_until_idle(timeout=90)
    cluster.stop_all()

    assert done == n
    assert pipe.warehouse.rows_loaded == n         # no loss, no duplicates
    drops = sum(rt.worker.buffer.dropped for rt in cluster.runtimes.values())
    assert drops == 0

    # same record set as the oracle: identity columns (equipment, window)
    # must match exactly; KPI columns may differ where a record was joined
    # against an earlier (still-correct) master version mid-stream
    oracle = sequential_oracle(n, n_partitions=10)
    a = pipe.warehouse.canonical_fact_table()
    b = oracle.warehouse.canonical_fact_table()
    assert a.shape == b.shape
    order = lambda t: t[np.lexsort((t[:, 2], t[:, 1], t[:, 0]))]
    np.testing.assert_array_equal(order(a)[:, :3], order(b)[:, :3])
    assert (a[:, -1] > 0.5).all()                  # every fact valid


def test_concurrent_scale_up_mid_stream():
    """Start with 1 worker, scale to 3 mid-run; the stream completes and
    newly added workers actually take over partitions."""
    n = 4000
    _, src, sampler, pipe = build(1, n, buffer_capacity=8192)
    feeder = threading.Thread(target=lambda: sampler.generate(src))
    cluster = ConcurrentCluster(pipe)
    cluster.start()
    feeder.start()
    time.sleep(0.1)
    cluster.scale_to(3)
    feeder.join()
    done = cluster.run_until_idle(timeout=60)
    cluster.stop_all()
    assert done == n
    assert len(cluster.alive_workers()) == 3
    owners = set(cluster.assignment.assignment.values())
    assert len(owners) == 3              # every worker owns partitions


def test_freshness_percentiles_recorded():
    """Every loaded record contributes one end-to-end freshness sample;
    percentiles are ordered and positive."""
    n = 2000
    _, src, sampler, pipe = build(2, n)
    sampler.generate(src)
    cluster = ConcurrentCluster(pipe)
    cluster.start()
    done = cluster.run_until_idle(timeout=60)
    cluster.stop_all()
    assert done == n
    lat = cluster.freshness()
    assert lat["n"] == n
    assert 0.0 < lat["p50_ms"] <= lat["p95_ms"] <= lat["p99_ms"]


def test_fetch_many_positions_vs_commits():
    """The broker's read-position / committed-offset split: fetch advances
    the position (no re-reads), commit is durable progress, and an
    abandoned read-ahead rewinds to the committed offset."""
    q = MessageQueue()
    q.create_topic(TopicConfig("t", 0, 2, "business_key"))
    n = 100
    q.publish("t", make_batch(0, 0, np.arange(n), np.arange(n),
                              np.arange(n), np.zeros((n, 8), np.float32)))
    batch1, counts1 = q.fetch_many("g", "t", [0, 1])
    assert sum(counts1.values()) == n
    # position advanced: nothing new to read, though nothing is committed
    batch2, counts2 = q.fetch_many("g", "t", [0, 1])
    assert not counts2
    assert all(q.committed("g", "t", p) == 0 for p in (0, 1))
    # a crash abandons the read-ahead: rewind, resume from committed
    for p in (0, 1):
        q.rewind("g", "t", p)
    batch3, counts3 = q.fetch_many("g", "t", [0, 1])
    assert sum(counts3.values()) == n
    np.testing.assert_array_equal(np.sort(batch3.row_key),
                                  np.sort(batch1.row_key))
    # commit makes it durable: fetch after rewind returns nothing
    for p, c in counts3.items():
        q.commit("g", "t", p, c)
        q.rewind("g", "t", p)
    _, counts4 = q.fetch_many("g", "t", [0, 1])
    assert not counts4


def test_concurrent_commits_are_exact():
    """Offset commits from many threads never lose an increment."""
    q = MessageQueue()
    q.create_topic(TopicConfig("t", 0, 1, "business_key"))
    per_thread, n_threads = 500, 8

    def worker():
        for _ in range(per_thread):
            q.commit("g", "t", 0, 1)

    threads = [threading.Thread(target=worker) for _ in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert q.committed("g", "t", 0) == per_thread * n_threads


def test_cdc_event_times_monotonic():
    """Event-time stamps are assigned at CDC append and are non-decreasing
    in LSN order — the foundation of the freshness metric."""
    src = SourceDatabase()
    for i in range(5):
        src.apply(make_batch(0, 0, np.arange(3) + 3 * i, np.zeros(3),
                             np.zeros(3), np.zeros((3, 8), np.float32)))
    lsns = np.arange(src.log.next_lsn)
    stamps = src.log.event_times(lsns)
    assert len(stamps) == 15
    assert (np.diff(stamps) >= 0).all()
    assert (stamps <= src.log.clock()).all()


# ---------------------------------------------- transform-stage coalescing
def _traced_pipe(n_workers, n_records, late_frac=0.05):
    from repro.observability.tracer import StageTracer
    cfg, src, sampler, _ = build(n_workers, n_records, late_frac=late_frac)
    tracer = StageTracer()
    pipe = DODETLPipeline(cfg, src, n_workers=n_workers, tracer=tracer)
    return cfg, src, sampler, pipe, tracer


class _HeldSource:
    """Collects the sampler's change batches, to apply them in pieces."""

    def __init__(self):
        self.batches = []

    def apply(self, batch):
        self.batches.append(batch)


def _wait_loaded(cluster, n, timeout=30.0):
    deadline = time.perf_counter() + timeout
    while cluster.records_done() < n:
        assert time.perf_counter() < deadline, (cluster.records_done(), n)
        time.sleep(0.002)


def _backlog(cluster_kw=None, resize=None):
    """A pre-extracted 3,000-record backlog; ``resize`` is applied once
    some hand-offs have merged, while the backlog still drains."""
    n = 3000
    n_workers = 2 if resize else 1
    cfg, src, sampler, pipe, tracer = _traced_pipe(n_workers, n)
    sampler.generate(src)
    pipe.extract()                      # everything queued before start
    cluster = ConcurrentCluster(pipe, poll_cdc=False, **(cluster_kw or {}))
    cluster.start()
    if resize:
        deadline = time.perf_counter() + 30.0
        while not pipe.metrics.counters().get("worker.handoffs_coalesced"):
            assert time.perf_counter() < deadline, "nothing merged"
            time.sleep(0.001)
        assert cluster.records_done() < n
        resize(cluster)
    done = cluster.run_until_idle(timeout=60)
    return cfg, pipe, cluster, tracer, n, done


def _trickle():
    """16 appends of 10 records, each to one unit (so one partition and
    one fetch), the cluster idle before the next."""
    cfg, src, sampler, pipe, tracer = _traced_pipe(1, 400, late_frac=0.0)
    held = _HeldSource()
    sampler.generate(held)
    masters, facts = held.batches[:2], held.batches[2]
    for batch in masters:
        src.apply(batch)
    cluster = ConcurrentCluster(pipe)
    cluster.start()
    n = 0
    for unit in range(8):
        rows = np.nonzero(facts.business_key == unit)[0]
        for lo in (0, 10):
            src.apply(facts.take(rows[lo:lo + 10]))
            n += 10
            _wait_loaded(cluster, n)
    done = cluster.run_until_idle(timeout=60)
    return cfg, pipe, cluster, tracer, n, done


_COALESCE_CASES = {
    "backlog": _backlog,
    "trickle": _trickle,
    "capped": lambda: _backlog({"max_records_per_partition": 100}),
    "scale_up": lambda: _backlog(resize=lambda c: c.scale_to(3)),
    "failover": lambda: _backlog(resize=lambda c: c.fail_workers(["w1"])),
}


@pytest.mark.parametrize("case", list(_COALESCE_CASES))
def test_transform_coalesces_queued_handoffs(case):
    """The transform stage merges the same-topic hand-offs already queued
    behind the one it takes into one dispatch: a backlog merges, never
    past the late buffer's capacity nor the per-partition cap's step
    bound; a trickle (one hand-off queued at a time) never merges; a
    rebalance while hand-offs merge loses and duplicates nothing, since
    a merged item retires every fetch ordinal it covers."""
    cfg, pipe, cluster, tracer, n, done = _COALESCE_CASES[case]()
    try:
        alive = [rt for rt in cluster.runtimes.values() if not rt.dead]
        assert all(rt.in_flight() == 0 for rt in alive)
    finally:
        cluster.stop_all()
    assert done == n
    assert pipe.warehouse.rows_loaded == n          # none lost, none twice

    events = [e for e in tracer.events() if e[0] == "X"]
    dispatch = [e[5] for e in events if e[1] == "transform.dispatch"]
    waits = [e[5] for e in events if e[1] == "transform.queue_wait"]
    merged = pipe.metrics.counters()["worker.handoffs_coalesced"]
    # every queued hand-off waited once and rode exactly one dispatch
    assert sum(d["handoffs"] for d in dispatch) == len(waits)
    assert sum(d["records"] for d in dispatch) == sum(
        w["records"] for w in waits)
    assert merged == len(waits) - len(dispatch)
    assert all(d["records"] <= cfg.buffer_capacity for d in dispatch)

    if case == "trickle":
        assert len(dispatch) == 16
        assert all(d["handoffs"] == 1 for d in dispatch)
        assert merged == 0
        return
    assert pipe.warehouse.canonical_fact_table().tobytes() == \
        sequential_oracle(n).warehouse.canonical_fact_table().tobytes()
    if case == "capped":
        assert all(d["records"] <= 100 * cfg.n_partitions for d in dispatch)
        return
    assert merged > 0
    assert any(d["handoffs"] > 1 for d in dispatch)
    if case == "backlog":
        (rt,) = alive
        w = rt.worker
        committed = sum(w.queue.committed(w.group, t, p)
                        for t in pipe.operational_topics
                        for p in range(cfg.n_partitions))
        assert committed == n
