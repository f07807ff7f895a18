"""Observability plane tests: lock-sharded registry vs a serial oracle,
tracer golden Chrome-trace output, NULL_TRACER zero-allocation pin,
bounded-reservoir determinism, and ClusterHealth consistency while
rebalance / repartition / checkpoint run concurrently."""
import dataclasses
import json
import threading
import time
import tracemalloc

import numpy as np
import pytest

from repro.configs.dod_etl import steelworks_config
from repro.core import DODETLPipeline, MessageQueue, SourceDatabase, \
    TopicConfig, make_batch
from repro.core.backend import NumpyBackend
from repro.core.metrics import LatencyRecorder, percentiles_ms
from repro.data.sampler import SamplerConfig, SteelworksSampler
from repro.observability import (NULL_TRACER, MetricsRegistry, StageTracer,
                                 global_registry)
from repro.observability.tracer import _NULL_SPAN
from repro.runtime.cluster import ConcurrentCluster


# ------------------------------------------------------------- registry
def test_registry_hammer_matches_serial_oracle():
    """8 writer threads, each on its own shard, hammering shared-name
    counters + histograms: the merged read equals a serial recount."""
    reg = MetricsRegistry()
    n_threads, n_iter = 8, 20_000

    def writer(i):
        shard = reg.shard(f"t{i}")
        c_shared = shard.counter("hits")       # same name on every shard
        c_own = shard.counter(f"own.{i}")
        h = shard.histogram("lat")
        for k in range(n_iter):
            c_shared.inc()
            c_own.inc(2)
            if k % 1000 == 0:
                h.add(np.full(10, float(i)))

    threads = [threading.Thread(target=writer, args=(i,))
               for i in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()

    counters = reg.counters()
    assert counters["hits"] == n_threads * n_iter     # summed across shards
    for i in range(n_threads):
        assert counters[f"own.{i}"] == 2 * n_iter
    # histogram union: every thread contributed (n_iter/1000)*10 samples
    p = reg.histogram_percentiles("lat")
    assert p["n"] == n_threads * (n_iter // 1000) * 10
    snap = reg.snapshot()
    assert snap["counters"] == counters
    assert "lat" in snap["histograms"]


def test_shard_handles_are_memoized_and_gauges_pull():
    reg = MetricsRegistry()
    s = reg.shard("w0")
    assert s is reg.shard("w0")
    assert s.counter("c") is s.counter("c")
    depth = [3]
    s.gauge_fn("queue_depth", lambda: depth[0])
    assert reg.gauges()["w0"]["queue_depth"] == 3.0
    depth[0] = 7
    assert reg.gauges()["w0"]["queue_depth"] == 7.0   # read-time evaluation
    s.gauge_fn("broken", lambda: 1 / 0)
    assert np.isnan(reg.gauges()["w0"]["broken"])     # never raises


def test_registered_histogram_is_adopted_not_copied():
    reg = MetricsRegistry()
    rec = LatencyRecorder()
    reg.shard("w0").register_histogram("freshness", rec)
    rec.add(np.array([0.1, 0.2, 0.3]))
    assert reg.histogram_percentiles("freshness")["n"] == 3
    rec.add(np.array([0.4]))
    assert reg.histogram_percentiles("freshness")["n"] == 4


def test_backend_counters_per_instance_shards_sum_globally():
    """Dispatch counters live on per-instance global-registry shards:
    per-instance reset stays isolated, merged reads sum the process."""
    a, b = NumpyBackend(), NumpyBackend()
    base = global_registry().counters().get("backend.numpy.op_dispatches", 0)
    a.op_dispatches += 3
    b.op_dispatches += 2
    assert a.op_dispatches == 3 and b.op_dispatches == 2
    merged = global_registry().counters()["backend.numpy.op_dispatches"]
    assert merged == base + 5
    a.reset_stats()
    assert a.op_dispatches == 0 and b.op_dispatches == 2


def test_broker_counters_and_commit_lags():
    q = MessageQueue()
    q.create_topic(TopicConfig("t", 0, 4, "business_key"))
    ids = np.arange(100, dtype=np.int64)
    q.publish("t", make_batch(0, 0, ids, ids % 7, ids + 100,
                              np.zeros((100, 8), np.float32)))
    counters = q.metrics.counters()
    assert counters["broker.t.published"] == 100
    assert counters["broker.t.key_loads"] == 100
    assert q.metrics.gauges()["broker.t"]["broker.t.high_watermark"] == 100
    lags = q.commit_lags("g")
    assert sum(lags["t"].values()) == 100        # nothing committed yet
    b = q.consume("g", "t", 0)
    q.commit("g", "t", 0, len(b))
    lags = q.commit_lags("g")
    assert lags["t"][0] == 0
    assert sum(lags["t"].values()) == 100 - len(b)


# --------------------------------------------------------------- tracer
def _tick_clock(step_s=0.5e-3):
    t = [0.0]

    def clock():
        v = t[0]
        t[0] += step_s
        return v
    return clock


def test_tracer_golden_chrome_trace_with_nesting():
    """Deterministic clock -> byte-stable Chrome-trace JSON: nested spans
    close inner-first, lanes become labeled tids, args ride along."""
    tracer = StageTracer(clock=_tick_clock(), cpu_clock=None)   # _t0 = 0.0
    with tracer.span("query.batch", lane="serving") as outer:   # t=0.5ms
        with tracer.span("serving.fold", lane="serving"):       # t=1.0ms
            pass                                                # t=1.5ms
        outer.put("queries", 2)
    # outer exit t=2.0ms
    tracer.instant("epoch.swap", lane="serving")                # t=2.5ms

    golden = {
        "traceEvents": [
            {"name": "process_name", "ph": "M", "pid": 1, "tid": 0,
             "args": {"name": "dod-etl"}},
            {"name": "thread_name", "ph": "M", "pid": 1, "tid": 1,
             "args": {"name": "serving"}},
            {"name": "serving.fold", "cat": "serving", "ph": "X",
             "ts": 1000.0, "pid": 1, "tid": 1, "dur": 500.0},
            {"name": "query.batch", "cat": "query", "ph": "X",
             "ts": 500.0, "pid": 1, "tid": 1, "dur": 1500.0,
             "args": {"queries": 2}},
            {"name": "epoch.swap", "cat": "epoch", "ph": "i",
             "ts": 2500.0, "pid": 1, "tid": 1, "s": "t"},
        ],
        "displayTimeUnit": "ms",
        "otherData": {"dropped_events": 0},
    }
    assert tracer.to_chrome() == golden
    # nesting containment: inner [ts, ts+dur] inside outer's interval
    ev = {e["name"]: e for e in golden["traceEvents"] if e["ph"] == "X"}
    inner, outer = ev["serving.fold"], ev["query.batch"]
    assert outer["ts"] <= inner["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"]
    json.dumps(tracer.to_chrome())                   # JSON-serializable


def test_tracer_drop_and_event_cap():
    tracer = StageTracer(max_events=2)
    with tracer.span("a") as sp:
        sp.drop()
    assert tracer.events() == []                     # dropped = not recorded
    for _ in range(4):
        with tracer.span("b"):
            pass
    assert len(tracer.events()) == 2                 # capped
    assert tracer.dropped_events == 2
    tracer.clear()
    assert tracer.events() == [] and tracer.dropped_events == 0


def test_tracer_export_file(tmp_path):
    tracer = StageTracer()
    with tracer.span("ingest.fetch", lane="w0.ingest") as sp:
        sp.put("records", 17)
    path = tracer.export_chrome_trace(str(tmp_path / "trace.json"))
    with open(path) as f:
        doc = json.load(f)
    names = [e["name"] for e in doc["traceEvents"]]
    assert "ingest.fetch" in names and "thread_name" in names


def test_null_tracer_zero_allocation():
    """The disabled seam allocates NOTHING per span: every call site gets
    the one shared _NullSpan, ``record()`` keeps nothing, and the worker
    step's nested call sites (``_tag`` on nested spans, hand-off waits)
    keep nothing either. Pinned with tracemalloc."""
    import repro.observability.tracer as tracer_mod
    import repro.runtime.cluster as cluster_mod
    from repro.runtime.cluster import _tag
    tr = NULL_TRACER
    assert tr.span("warmup") is _NULL_SPAN           # shared singleton
    assert tr.record("warmup", 0.0, 1.0, records=1, batch=0) is None

    def step(k):
        with tr.span("x") as sp:
            sp.put("k", 1)
            sp.drop()
        tr.instant("y")
        tr.record("transform.queue_wait", 0.0, time.perf_counter(),
                  records=4096, batch=k)
        with tr.span("transform.dispatch") as sp:
            with tr.span("transform.snapshot") as ss:
                _tag(ss, 4096, k)
                ss.put("upload_bytes", 0)
            with tr.span("transform.launch") as sl:
                _tag(sl, 4096, k)
            _tag(sp, 4096, k)

    for k in range(100):                             # warm any caches
        step(k)
    tracemalloc.start()
    snap1 = tracemalloc.take_snapshot()
    for k in range(10_000):
        step(k)
    snap2 = tracemalloc.take_snapshot()
    tracemalloc.stop()
    files = (tracer_mod.__file__, cluster_mod.__file__, __file__)
    grown = sum(s.size_diff for s in snap2.compare_to(snap1, "filename")
                if s.traceback[0].filename in files and s.size_diff > 0)
    # zero PER-SPAN allocation: 10k steps may leave at most a constant
    # few transient blocks (bound methods caught mid-flight by the
    # snapshot), never anything proportional to the step count. One
    # real span object per iteration would show >= 560 KB here.
    assert grown < 256
    assert tr.enabled is False


@pytest.mark.parametrize("kind", ["stage_tracer", "bench_annotated"])
def test_spans_are_profiler_annotations_once(kind, tmp_path):
    """Under a ``jax.profiler`` trace on the CPU, every recorded span is
    one host annotation, both with the program's own ``StageTracer`` and
    with the benchmark's ``annotated_tracer`` (which annotates by
    itself): each span shows once, never twice. ``record()`` spans are
    not annotations."""
    import jax
    from jax.profiler import ProfileData
    if kind == "stage_tracer":
        tracer = StageTracer()
    else:
        import sys
        from pathlib import Path
        root = str(Path(__file__).resolve().parents[1])
        if root not in sys.path:
            sys.path.insert(0, root)
        from bench.harness import annotated_tracer
        tracer = annotated_tracer()
    jax.profiler.start_trace(str(tmp_path))
    try:
        for k in range(7):
            tracer.record("transform.queue_wait", time.perf_counter(),
                          time.perf_counter(), records=3, batch=k)
            with tracer.span("transform.dispatch"):
                with tracer.span("transform.snapshot"):
                    pass
                with tracer.span("transform.launch"):
                    jax.numpy.zeros(3).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    path = sorted(tmp_path.rglob("*.xplane.pb"))[-1]
    seen = {}
    for plane in ProfileData.from_file(str(path)).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    seen[ev.name] = seen.get(ev.name, 0) + 1
    recorded = {}
    for ev in tracer.events():
        recorded[ev[1]] = recorded.get(ev[1], 0) + 1
    assert recorded["transform.dispatch"] == 7
    for name in ("transform.dispatch", "transform.snapshot",
                 "transform.launch"):
        assert seen.get(name) == recorded[name], (name, seen.get(name))
    assert "transform.queue_wait" not in seen


# ---------------------------------------------------- bounded reservoir
def test_reservoir_non_overflow_path_is_exact():
    """At or under capacity the recorder is byte-identical to the legacy
    keep-everything behavior."""
    rec = LatencyRecorder()
    rng = np.random.default_rng(0)
    a, b = rng.normal(size=300) ** 2, rng.normal(size=500) ** 2
    rec.add(a)
    rec.add(b)
    full = np.concatenate([a, b])
    assert rec.merged(drain=False).tobytes() == full.tobytes()
    assert rec.percentiles() == percentiles_ms(full)
    assert rec.total_seen == 800 and rec.stored == 800


def test_reservoir_overflow_keeps_deterministic_stride_subset():
    """Past capacity: kept samples are EXACTLY the arrivals whose global
    index is divisible by the (power-of-two) stride — independent of how
    arrivals were chunked."""
    samples = np.arange(1000, dtype=np.float64)
    chunkings = [[1000], [37, 463, 500], [1] * 1000, [999, 1]]
    merged_views = []
    for chunks in chunkings:
        rec = LatencyRecorder(capacity=64)
        off = 0
        for n in chunks:
            rec.add(samples[off:off + n])
            off += n
        stride = rec._stride
        assert stride & (stride - 1) == 0 and stride > 1
        expect = samples[::stride]
        got = rec.merged(drain=False)
        assert got.tobytes() == expect.tobytes()
        assert rec.stored <= rec.capacity
        assert rec.total_seen == 1000
        merged_views.append(got.tobytes())
    assert len(set(merged_views)) == 1               # chunking-invariant


def test_reservoir_drain_resets_stride():
    rec = LatencyRecorder(capacity=16)
    rec.add(np.arange(100, dtype=np.float64))
    assert rec._stride > 1
    drained = rec.merged(drain=True)
    assert len(drained) <= 16
    assert rec.stored == 0 and rec._stride == 1
    rec.add(np.arange(5, dtype=np.float64))
    assert rec.merged().tobytes() == \
        np.arange(5, dtype=np.float64).tobytes()


# --------------------------------------------- live cluster integration
def _build(n_workers, n_records=3000, n_partitions=8, late_frac=0.05,
           buffer_capacity=8192):
    cfg = steelworks_config(n_partitions=n_partitions, backend="numpy")
    cfg = dataclasses.replace(cfg, buffer_capacity=buffer_capacity)
    src = SourceDatabase()
    sampler = SteelworksSampler(cfg, SamplerConfig(
        records_per_table=n_records, n_equipment=n_partitions,
        late_master_frac=late_frac))
    return cfg, src, sampler


def test_cluster_trace_covers_all_six_stage_seams(tmp_path):
    """Sustained load with serving + checkpointing: the exported trace
    holds spans for every stage seam, in worker-thread lanes, and loads
    as valid Chrome-trace JSON."""
    from repro.durability.journal import DurabilityJournal
    from repro.durability.recovery import RecoveryCoordinator
    from repro.serving.batch import BatchedReportServer, ReportQuery
    from repro.serving.engine import MaterializedViewEngine
    from repro.serving.server import ReportServer
    from repro.serving.views import steelworks_views

    cfg, src, sampler = _build(2)
    tracer = StageTracer()
    pipe = DODETLPipeline(cfg, src, n_workers=2, tracer=tracer)
    engine = MaterializedViewEngine(steelworks_views(20), backend="numpy")
    front = BatchedReportServer(ReportServer(engine))
    rec = RecoveryCoordinator(DurabilityJournal(str(tmp_path / "j")))
    cluster = ConcurrentCluster(pipe, serving=front, recovery=rec)
    sampler.generate(src)
    cluster.start()
    done = cluster.run_until_idle(timeout=60)
    cluster.checkpoint()
    front.submit(ReportQuery(kind="oee")).result(5.0)
    cluster.stop_all()
    assert done == 3000

    names = set(tracer.span_names())
    assert {"ingest.fetch", "transform.dispatch", "load.commit",
            "serving.fold", "query.batch", "checkpoint.step"} <= names
    doc = tracer.to_chrome()
    lanes = {e["args"]["name"] for e in doc["traceEvents"]
             if e["name"] == "thread_name"}
    assert any(l.endswith(".ingest") for l in lanes)
    assert any(l.endswith(".transform") for l in lanes)
    assert any(l.endswith(".load") for l in lanes)
    for e in doc["traceEvents"]:
        if e["ph"] == "X":
            assert e["dur"] >= 0.0 and e["ts"] >= 0.0
    json.loads(json.dumps(doc))                      # round-trips


# the per-batch seams of a worker step: (name -> parents it nests in)
_NESTED = {"transform.snapshot": ("transform.dispatch",),
           "transform.launch": ("transform.dispatch",),
           "load.to_host": ("load.commit", "load.retry"),
           "load.warehouse": ("load.commit", "load.retry")}
_PER_BATCH = ("ingest.fetch", "transform.dispatch", "load.commit",
              "load.retry") + tuple(_NESTED)


class _HeldSource:
    """Collects the sampler's change batches, to apply them in phases."""

    def __init__(self):
        self.batches = []

    def apply(self, batch):
        self.batches.append(batch)


def _traced_cluster_events():
    """Two workers drain 3,000 records; the 5% late master tail reaches
    the log only once the production facts have been processed, so the
    late records go through the retry path."""
    cfg, src, sampler = _build(2)
    tracer = StageTracer()
    pipe = DODETLPipeline(cfg, src, n_workers=2, tracer=tracer)
    # a per-partition cap splits each worker's share into several batches
    cluster = ConcurrentCluster(pipe, max_records_per_partition=100)
    held = _HeldSource()
    sampler.generate(held)
    for batch in held.batches[:3]:      # masters (less the tail), facts
        src.apply(batch)
    cluster.start()
    try:
        early = cluster.run_until_idle(timeout=60, stall_s=1.0)
        for batch in held.batches[3:]:  # the late master tail
            src.apply(batch)
        done = cluster.run_until_idle(timeout=60)
    finally:
        cluster.stop_all()
    assert early < 3000 and done == 3000
    return tracer.events()


def test_cluster_trace_nests_worker_step_seams():
    """The worker step's sub-spans: every new seam appears; each nested
    seam lies inside its parent's interval on the same lane, with the
    parent's batch; the children of one parent sum to no more than it;
    every per-batch span carries ``records``, ``batch`` and a ``cpu_s``
    within [0, dur]; each hand-off wait starts at or after the start of
    the stage that produced its batch."""
    events = [e for e in _traced_cluster_events() if e[0] == "X"]
    names = {e[1] for e in events}
    assert {"ingest.pump", "transform.queue_wait", "transform.snapshot",
            "transform.launch", "load.queue_wait", "load.to_host",
            "load.warehouse", "load.retry"} <= names

    children = {}                       # parent event index -> child durs
    for e in events:
        _, name, lane, t0, dur, args = e
        if name in _PER_BATCH:
            assert {"records", "batch", "cpu_s"} <= set(args), e
            assert 0.0 <= args["cpu_s"] <= dur, e
        if name == "ingest.pump":
            assert args["rows"] > 0 and 0.0 <= args["cpu_s"] <= dur
        if name in _NESTED:
            parents = [i for i, p in enumerate(events)
                       if p[1] in _NESTED[name] and p[2] == lane
                       and p[3] <= t0 and t0 + dur <= p[3] + p[4]]
            assert len(parents) == 1, e
            parent = events[parents[0]]
            assert parent[5]["batch"] == args["batch"], (e, parent)
            children.setdefault(parents[0], []).append(dur)
    assert children
    for i, durs in children.items():
        assert sum(durs) <= events[i][4], (events[i], durs)

    def start_of(name, worker, batch):
        found = [e[3] for e in events if e[1] == name
                 and e[2].split(".")[0] == worker
                 and e[5]["batch"] == batch]
        assert len(found) == 1, (name, worker, batch)
        return found[0]

    waits = 0
    for _, name, lane, t0, dur, args in events:
        if name.endswith(".queue_wait"):
            assert {"records", "batch"} <= set(args)
            assert dur >= 0.0
            worker = lane.split(".")[0]
            cause = ("ingest.fetch" if name == "transform.queue_wait"
                     else "transform.launch")
            assert t0 >= start_of(cause, worker, args["batch"])
            waits += 1
    assert waits >= 2


def test_snapshot_view_reports_upload_bytes():
    """``transform.snapshot``'s ``upload_bytes``: the first device
    snapshot uploads all three arrays, a memoized one nothing, and an
    update of existing keys only the values and txn times."""
    from repro.core.cache import InMemoryTable
    tbl = InMemoryTable(64, backend="jax")
    keys = np.arange(10, dtype=np.int64)
    rows = np.ones((10, tbl.width), np.float32)
    tbl.upsert(keys, rows, np.arange(10, dtype=np.int64))
    snap = tbl.snapshot_view(True)
    k, v, t = snap.device_state()
    assert tbl.upload_bytes == k.nbytes + v.nbytes + t.nbytes > 0
    assert tbl.snapshot_view(True) is snap and tbl.upload_bytes == 0
    tbl.upsert(keys, 2 * rows, np.arange(10, dtype=np.int64) + 100)
    tbl.snapshot_view(True)
    assert tbl.upload_bytes == v.nbytes + t.nbytes
    tbl.snapshot_view(False)                 # host snapshot: a copy
    assert tbl.upload_bytes == 0


def test_health_consistent_during_rebalance_and_checkpoint(tmp_path):
    """Poll health() as fast as possible while a feeder streams, workers
    fail over, the cluster repartitions and checkpoints run: every
    snapshot is internally consistent (partition ownership forms a
    partition of the partition set, counters monotone, lags
    non-negative) and no poll ever raises."""
    from repro.durability.journal import DurabilityJournal
    from repro.durability.recovery import RecoveryCoordinator

    n = 6000
    cfg, src, sampler = _build(4, n_records=n, n_partitions=8)
    pipe = DODETLPipeline(cfg, src, n_workers=4)
    rec = RecoveryCoordinator(DurabilityJournal(str(tmp_path / "j")))
    cluster = ConcurrentCluster(pipe, recovery=rec)

    snaps, errors = [], []
    stop = threading.Event()

    def poller():
        while not stop.is_set():
            try:
                snaps.append(cluster.health())
            except Exception as exc:      # pragma: no cover - must not fire
                errors.append(exc)
                return

    feeder = threading.Thread(target=lambda: sampler.generate(src))
    poll_t = threading.Thread(target=poller)
    cluster.start()
    feeder.start()
    poll_t.start()
    time.sleep(0.1)                       # mid-stream, under load
    cluster.checkpoint()
    cluster.fail_workers(["w1"])          # rebalance while polling
    cluster.repartition()
    cluster.checkpoint()
    cluster.scale_to(4)
    feeder.join()
    done = cluster.run_until_idle(timeout=120)
    stop.set()
    poll_t.join(5.0)
    final = cluster.health()
    cluster.stop_all()

    assert not errors
    assert done == n
    assert len(snaps) > 5
    all_parts = set(range(8))
    prev_done = -1
    for h in snaps + [final]:
        owned = [p for w in h["workers"].values() for p in w["partitions"]]
        assert len(owned) == len(set(owned))         # disjoint ownership
        assert set(owned) <= all_parts
        for lags in h["commit_lag"].values():
            assert all(v >= 0 for v in lags.values())
        assert h["backlog"]["operational_lag"] >= 0
        total_done = sum(w["records_done"] for w in h["workers"].values())
        assert total_done >= 0
        prev_done = max(prev_done, total_done)
    # the final post-idle snapshot reflects the drained stream
    assert set(p for w in final["workers"].values()
               for p in w["partitions"]) == all_parts
    assert final["backlog"]["operational_lag"] == 0
    assert final["checkpoint"]["steps"] == 2
    assert final["checkpoint"]["age_s"] is not None
    assert final["counters"]["pipeline.checkpoints"] == 2
    assert final["counters"]["pipeline.repartitions"] == 1
    assert final["freshness"]["n"] > 0
    sum_hits = final["counters"]["worker.cache_hits"]
    assert sum_hits >= n                 # every record joined at least once


def test_pipeline_health_sequential():
    cfg, src, sampler = _build(2, n_records=1500)
    pipe = DODETLPipeline(cfg, src, n_workers=2)
    sampler.generate(src)
    pipe.extract()
    pipe.bootstrap_caches()
    pipe.run_to_completion()
    h = pipe.health()
    assert sum(w["records_done"] for w in h["workers"].values()) == 1500
    assert h["backlog"]["operational_lag"] == 0
    assert h["counters"]["worker.cache_hits"] == 1500
    owned = [p for w in h["workers"].values() for p in w["partitions"]]
    assert sorted(owned) == list(range(8))


def test_default_tracer_is_null_and_emits_nothing():
    cfg, src, sampler = _build(1, n_records=500)
    pipe = DODETLPipeline(cfg, src, n_workers=1)
    assert pipe.tracer is NULL_TRACER
    sampler.generate(src)
    pipe.extract()
    pipe.bootstrap_caches()
    pipe.run_to_completion()
    assert pipe.warehouse.rows_loaded == 500         # seam is transparent
