"""Bring-up check of the DOD-ETL main path on a TPU, through the entry
points a deployment uses.

    python chip_smoke.py             # one chip: phases 0, 1 and 2
    python chip_smoke.py --mesh 4    # four chips: the sharded serving plane

Phase 0 reports the devices and exits non-zero unless JAX's default
platform is a TPU; there is no CPU fallback.

Phase 1 runs the paper's steelworks deployment (20 equipment units, 20
partitions, 100,000 records per source table) through five concurrent
workers with the materialized report views attached, on the default
``jax`` backend, then serves a dashboard burst through the batched query
front. It checks every production record loaded, nothing dead-lettered,
the facts against a sequential ``numpy`` pipeline on the same data, the
views against a warehouse rescan, and every batched answer against the
same query asked alone.

Phase 2 runs the ``pallas`` backend's kernels, compiled, on one worker
step against phase 1's caches and compares them with ``numpy``.

``--mesh N`` runs only the sharded serving plane on an N-device mesh and
compares it bitwise with the single-device view engine.

Wall times printed on the way are one run's readings, not metrics. The
last line of standard output is the result: one JSON object naming the
device. Any failed check exits non-zero before it.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import numpy as np  # noqa: E402

N_UNITS = 20                 # paper §4: 20 equipment units = partitions
N_WORKERS = 5
RECORDS_PER_TABLE = 100_000
# the busiest worker caches 8 of the 20 units: 40,000 quality rows, 31%
# of 2^17 slots, so no probe chain outgrows the 16-probe budget and no
# InMemoryTable._grow() fires mid-run
CACHE_SLOTS = 1 << 17
STEP_RECORDS = 8192          # phase 2: one worker step
BURST_QUERIES = 4000
# tests/test_backends.py's pallas-vs-numpy tolerance
RTOL = ATOL = 1e-5


class SmokeFailure(AssertionError):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def compare(name: str, got, want, *, rtol: float, atol: float) -> bool:
    """Bitwise where it holds, else within the tolerance. Prints which,
    with the worst error. Returns True when bitwise."""
    got = np.asarray(got)
    want = np.asarray(want)
    check(got.shape == want.shape, f"{name}: shape {got.shape} != "
          f"{want.shape}")
    g = got.astype(np.float64)
    w = want.astype(np.float64)
    if got.dtype == want.dtype and got.tobytes() == want.tobytes():
        print(f"    {name}: bitwise equal")
        return True
    same_nan = np.isnan(g) == np.isnan(w)
    check(bool(same_nan.all()), f"{name}: NaN positions differ")
    both = ~np.isnan(g)
    g, w = g[both], w[both]
    with np.errstate(invalid="ignore"):      # inf - inf where both agree
        err = np.where(g == w, 0.0, np.abs(g - w))
    worst = float(err.max()) if err.size else 0.0
    bound = atol + rtol * np.abs(w)
    ok = bool((err <= bound).all())
    print(f"    {name}: not bitwise; worst abs error {worst!r} "
          f"(rtol {rtol}, atol {atol}) {'ok' if ok else 'FAILED'}")
    check(ok, f"{name}: worst abs error {worst!r} exceeds tolerance")
    return False


# ------------------------------------------------------------------ phase 0
def phase0(want_devices: int):
    import jax
    print(f"phase 0: jax {jax.__version__}, default backend "
          f"{jax.default_backend()}, devices {jax.devices()}")
    if jax.default_backend() != "tpu":
        sys.exit(f"chip_smoke: no TPU: JAX's default backend is "
                 f"{jax.default_backend()!r}; this check runs on the chip "
                 f"only")
    if len(jax.devices()) < want_devices:
        sys.exit(f"chip_smoke: {want_devices} devices wanted, "
                 f"{len(jax.devices())} found")
    return jax.devices()


# ------------------------------------------------------------------ phase 1
def build_deployment(records: int, slots: int, seed: int, backend: str = ""):
    from repro.configs.dod_etl import steelworks_config
    from repro.core import SourceDatabase
    from repro.data.sampler import SamplerConfig, SteelworksSampler
    cfg = dataclasses.replace(
        steelworks_config(n_partitions=N_UNITS, backend=backend),
        cache_slots=slots)
    src = SourceDatabase()
    SteelworksSampler(cfg, SamplerConfig(
        records_per_table=records, n_equipment=N_UNITS,
        seed=seed)).generate(src)
    return cfg, src


def single_query(rsnap, q):
    """The per-query answer a batched answer must reproduce."""
    return {"view": lambda: rsnap.query(q.view),
            "oee": lambda: rsnap.oee(q.unit),
            "top_downtime": lambda: rsnap.top_downtime(q.k),
            "production_rate": rsnap.production_rate,
            "production_curve": rsnap.production_curve,
            "shift_report": rsnap.shift_report,
            "kpi_rollup": rsnap.kpi_rollup}[q.kind]()


def phase1(records: int = RECORDS_PER_TABLE, slots: int = CACHE_SLOTS,
           burst: int = BURST_QUERIES, seed: int = 0):
    from repro.core import DODETLPipeline
    from repro.runtime.cluster import ConcurrentCluster
    from repro.serving import (BatchedReportServer, MaterializedViewEngine,
                               ReportQuery, ReportServer, ReportSnapshot,
                               steelworks_views)
    print(f"phase 1: steelworks deployment, {N_UNITS} units, "
          f"{N_WORKERS} workers, {records} records per table, "
          f"{slots} cache slots per table")
    cfg, src = build_deployment(records, slots, seed)
    pipe = DODETLPipeline(cfg, src, n_workers=N_WORKERS)
    check(pipe.backend.name == "jax", f"backend {pipe.backend.name}")
    engine = MaterializedViewEngine(steelworks_views(N_UNITS))
    # everything queued before start: every worker joins each record
    # against the final master versions, which the sequential oracle
    # below does too
    pipe.extract()
    cluster = ConcurrentCluster(pipe, poll_cdc=False, serving=engine)
    t0 = time.perf_counter()
    cluster.start()
    try:
        cluster.run_until_idle(timeout=900, stall_s=30.0)
    finally:
        cluster.stop_all()
    print(f"  stream to idle, compiles included: "
          f"{time.perf_counter() - t0:.1f} s (one run)")

    wh = pipe.warehouse
    for w in pipe.workers:
        print(f"  {w.name}: partitions {list(w.partitions)}; equipment "
              f"{w.equipment.n_rows} rows / {w.equipment.n_slots} slots, "
              f"quality {w.quality.n_rows} rows / {w.quality.n_slots} slots")
        check(w.equipment.n_slots == slots and w.quality.n_slots == slots,
              f"{w.name}: a cache grew mid-run")
    dead = sum(len(rt.worker.dead_letter) for rt in cluster.runtimes.values())
    counters = cluster.health()["counters"]
    buffered = sum(len(w.buffer) for w in pipe.workers)
    print(f"  loaded {wh.rows_loaded} of {records} production records; "
          f"dead-lettered {dead} (counter "
          f"{counters.get('worker.dead_lettered', 0)}); late-buffered "
          f"{buffered}")
    check(wh.rows_loaded == records, "not every production record loaded")
    check(dead == 0 and counters.get("worker.dead_lettered", 0) == 0,
          "records were dead-lettered")

    # sequential numpy oracle over the same change log
    t0 = time.perf_counter()
    ocfg = dataclasses.replace(cfg, backend="numpy")
    oracle = DODETLPipeline(ocfg, src, n_workers=1)
    oracle.extract()
    oracle.bootstrap_caches()
    oracle.run_to_completion()
    print(f"  numpy oracle: {oracle.warehouse.rows_loaded} facts "
          f"({time.perf_counter() - t0:.1f} s)")
    got = wh.canonical_fact_table()
    want = oracle.warehouse.canonical_fact_table()
    check(got.shape == want.shape, f"fact table {got.shape} vs oracle "
          f"{want.shape}")
    from repro.core.transformer import FACT_COLUMNS
    print("  facts vs numpy oracle, per column:")
    for j, col in enumerate(FACT_COLUMNS):
        compare(col, got[:, j], want[:, j], rtol=RTOL, atol=ATOL)

    # views vs the warehouse rescan
    snap = ReportServer(engine).snapshot()
    print(f"  views at epoch {snap.epoch}, {snap.snap.rows_folded} facts "
          f"folded; vs warehouse rescan:")
    rescan = wh.kpi_rollup(N_UNITS, backend="numpy")
    view = snap.kpi_rollup()
    compare("kpi_rollup count", view[:, 4], rescan[:, 4], rtol=0, atol=0)
    compare("kpi_rollup sums", view[:, :4], rescan[:, :4], rtol=RTOL,
            atol=ATOL)
    running = wh.kpi_running()
    check(running is not None, "loads arrived without fused rollups")
    compare("kpi_running (fused rollups)", running, rescan, rtol=1e-4,
            atol=1e-4)
    lanes = ("availability", "performance", "quality", "oee", "rows")
    oee_view = np.array([[snap.oee(u).data[k] for k in lanes]
                         for u in range(N_UNITS)])
    oee_scan = np.array([[wh.query_oee(u)[k] for k in lanes]
                         for u in range(N_UNITS)])
    compare("query_oee per unit", oee_view, oee_scan, rtol=RTOL, atol=ATOL)

    # dashboard burst through the batched front
    queries = ([ReportQuery("oee", unit=i % N_UNITS)
                for i in range(burst - 40)]
               + [ReportQuery("top_downtime", k=5), ReportQuery("oee"),
                  ReportQuery("shift_report"), ReportQuery("production_rate"),
                  ReportQuery("production_curve"), ReportQuery("kpi_rollup"),
                  ReportQuery("view", view="kpi_by_unit_shift"),
                  ReportQuery("view", view="downtime_by_equipment")] * 5)
    front = BatchedReportServer(ReportServer(engine), max_batch=4096,
                                max_wait_ms=2.0)
    front.start()
    try:
        t0 = time.perf_counter()
        tickets = [front.submit(q) for q in queries]
        answers = [t.result(timeout=120.0) for t in tickets]
        burst_s = time.perf_counter() - t0
    finally:
        front.stop()
    st = front.stats()
    print(f"  dashboard burst: {len(queries)} queries in {burst_s:.3f} s "
          f"(one run), {st['batches']} batch(es)")
    batched, alone = {}, {}
    for q, t, a in zip(queries, tickets, answers):
        want = single_query(ReportSnapshot(t.snapshot), q)
        if q.kind == "kpi_rollup":
            pairs = [("kpi_rollup", a.data["kpi_rollup"], want)]
        else:
            check(a.epoch == want.epoch and a.rows == want.rows,
                  f"{q.kind}: epoch/rows differ")
            check(set(a.data) == set(want.data), f"{q.kind}: keys differ")
            pairs = [(f"{q.kind}.{k}", a.data[k], v)
                     for k, v in want.data.items() if k != "lanes"]
        for key, g, w in pairs:
            batched.setdefault(key, []).append(np.asarray(g, np.float64)
                                               .ravel())
            alone.setdefault(key, []).append(np.asarray(w, np.float64)
                                             .ravel())
    print("  batched vs per-query answers:")
    for key in sorted(batched):
        compare(key, np.concatenate(batched[key]),
                np.concatenate(alone[key]), rtol=RTOL, atol=ATOL)
    return pipe, engine


# ------------------------------------------------------------------ phase 2
def phase2(pipe, engine, step: int = STEP_RECORDS):
    from repro.core.backend import NumpyBackend, PallasBackend
    from repro.core.device import pallas_interpret
    from repro.core.transformer import FACT_COLUMNS
    from repro.kernels.hash_join.hash_join import hash_join_kernel
    from repro.serving import steelworks_views
    on_tpu = not pallas_interpret()
    print(f"phase 2: pallas backend, kernels "
          f"{'compiled' if on_tpu else 'interpreted'}")
    # the worker with the largest caches; one step's coalesced read of its
    # partitions, by a consumer group of its own (no offset moves)
    w = max(pipe.workers, key=lambda w: w.quality.n_rows)
    topic = pipe.operational_topics[0]
    batch, _ = pipe.queue.consume_many(
        "chip_smoke.phase2", topic, w.partitions,
        step // max(len(w.partitions), 1))
    prod = batch.payload
    print(f"  {w.name}: {len(prod)} records against {w.quality.n_rows} "
          f"quality rows in {w.quality.n_slots} slots")
    pal, ref = PallasBackend(), NumpyBackend()
    if on_tpu:
        import jax.numpy as jnp
        keys = jnp.zeros((len(prod) + (-len(prod)) % 256,), jnp.int32)
        text = hash_join_kernel.lower(
            keys, *w.quality.device_state(), interpret=False).as_text()
        check("tpu_custom_call" in text, "hash_join lowered without Mosaic")

    t0 = time.perf_counter()
    got = pal.transform_and_rollup(prod, w.equipment, w.quality,
                                   n_units=N_UNITS)
    facts, found = got.to_host()
    print(f"  transform_and_rollup (hash_join x2 + segment_kpi), compile "
          f"included: {time.perf_counter() - t0:.2f} s (one run)")
    want = ref.transform_and_rollup(prod, w.equipment, w.quality,
                                    n_units=N_UNITS)
    wfacts, wfound = want.to_host()
    check(bool(found.any()), "no record joined")
    compare("found", found, wfound, rtol=0, atol=0)
    for j, col in enumerate(FACT_COLUMNS):
        compare(f"facts.{col}", facts[:, j], wfacts[:, j], rtol=RTOL,
                atol=ATOL)
    compare("rollup", got.rollup_host(), want.rollup_host(), rtol=RTOL,
            atol=1e-4)

    qkeys = prod[:, 0].astype(np.int64)
    gv, gf, gt = pal.hash_probe(qkeys, *w.quality.device_state())
    wv, wf, wt = ref.hash_probe(qkeys, w.quality.keys, w.quality.values,
                                w.quality.txn)
    compare("hash_probe found", gf, wf, rtol=0, atol=0)
    compare("hash_probe values", gv, wv, rtol=0, atol=0)
    compare("hash_probe txn", gt.astype(np.int64), wt, rtol=0, atol=0)

    good = facts[found]
    compare("segment_reduce", pal.segment_reduce(good, N_UNITS),
            ref.segment_reduce(good, N_UNITS), rtol=RTOL, atol=1e-4)
    rng = np.random.default_rng(0)
    snap = engine.snapshot()
    for spec in steelworks_views(N_UNITS):
        valid = good[good[:, 9] > 0.5]
        seg, vals = spec.segments(valid), spec.values(valid)
        compare(f"fold_segments {spec.name}",
                pal.fold_segments(seg, vals, spec.n_segments),
                ref.fold_segments(seg, vals, spec.n_segments),
                rtol=RTOL, atol=ATOL)
        table = snap.view(spec.name).table
        idx = rng.integers(0, spec.n_segments, 4096)
        got_g = pal.batch_gather_stats(table, idx)
        want_g = ref.batch_gather_stats(table, idx)
        # the selected stats come from one-hot matmuls (count, sums) and
        # masked lanes (mins, maxs); the means are divisions after them
        sel = 1 + 3 * spec.n_lanes
        compare(f"batch_gather_stats {spec.name} count|sums|mins|maxs",
                got_g[:, :sel], want_g[:, :sel], rtol=RTOL, atol=ATOL)
        compare(f"batch_gather_stats {spec.name} means",
                got_g[:, sel:], want_g[:, sel:], rtol=RTOL, atol=ATOL)


# --------------------------------------------------------------- mesh phase
def phase_mesh(n_shards: int, records: int = RECORDS_PER_TABLE,
               seed: int = 0):
    from repro.core.backend import ComputeBackend, get_backend
    from repro.data.sampler import synthetic_facts
    from repro.launch.mesh import make_shard_mesh
    from repro.runtime.shard_plane import ShardedViewEngine
    from repro.serving import MaterializedViewEngine, steelworks_views
    print(f"mesh phase: sharded serving plane on {n_shards} devices, "
          f"{records} facts in deltas of 1024")
    specs = steelworks_views(N_UNITS)
    be = get_backend("jax")
    mesh = make_shard_mesh(n_shards)
    sharded = ShardedViewEngine(specs, n_shards=n_shards, backend="jax")
    plain = MaterializedViewEngine(specs, backend="jax")
    rng = np.random.default_rng(seed)
    deltas = [synthetic_facts(rng, 1024, N_UNITS, valid_frac=0.95)
              for _ in range(max(records // 1024, 1))]
    be.set_mesh(mesh)
    # every [K, S, W] stack the engine's folds produce, by the devices that
    # hold its shards: the mesh path ran, and ran spread over the mesh
    mesh_fold, placed = be._mesh_fold, []

    def recorded(*args, **kwargs):
        stacked = mesh_fold(*args, **kwargs)
        placed.append(frozenset(s.device.id
                                for s in stacked.addressable_shards))
        return stacked

    be._mesh_fold = recorded
    try:
        t0 = time.perf_counter()
        for d in deltas:
            sharded.publish(d)
            plain.publish(d)
        sharded.fold_pending()
        plain.fold_pending()
        print(f"  folded {len(deltas)} deltas x {len(specs)} views, "
              f"compiles included: {time.perf_counter() - t0:.1f} s "
              f"(one run)")
        print(f"  {len(placed)} mesh folds, on devices "
              f"{sorted(set().union(*placed))}")
        check(len(placed) >= len(specs), "the mesh fold never ran")
        check(all(len(p) == n_shards for p in placed),
              "a mesh fold was not spread over the mesh")
        spec, d = specs[0], deltas[0]
        owners = sharded.ownership.seg_owners(spec.name)
        compare("mesh fold vs host reference",
                be.fold_segments_sharded(spec.segments(d), spec.values(d),
                                         spec.n_segments, owners, n_shards),
                ComputeBackend.fold_segments_sharded(
                    be, spec.segments(d), spec.values(d), spec.n_segments,
                    owners, n_shards), rtol=0, atol=0)
    finally:
        be.set_mesh(None)
    s, p = sharded.snapshot(), plain.snapshot()
    check(s.epoch >= 1 and s.rows_folded == p.rows_folded,
          "sharded engine folded different rows")
    for spec in specs:
        bitwise = compare(f"sharded vs single-device {spec.name}",
                          s.view(spec.name).table, p.view(spec.name).table,
                          rtol=0, atol=0)
        check(bitwise, f"{spec.name}: sharded state not bitwise")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--mesh", type=int, default=0, metavar="N",
                    help="run only the sharded serving plane on N devices")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    from repro.core.device import enable_compile_cache
    print(f"compile cache: {enable_compile_cache()}")
    devices = phase0(max(args.mesh, 1))
    if args.mesh:
        phase_mesh(args.mesh, seed=args.seed)
    else:
        pipe, engine = phase1(seed=args.seed)
        phase2(pipe, engine)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
