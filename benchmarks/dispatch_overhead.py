"""Device-plane dispatch/sync overhead benchmark — the measurement suite
of the zero-copy ``FactBlock`` hot path (-> ``BENCH_dispatch.json``).

Three measurements, all on the jax backend by default:

  * ``round_trips``     — instrumented backend counters
                          (``op_dispatches`` / ``host_syncs``) over one
                          representative BI step on a fixed fact block:
                          the PRE-PR op sequence (transform with an
                          immediate host sync, a separate per-unit
                          ``segment_reduce`` round trip, a serving-layer
                          delta fold) against the DEVICE PLANE (one fused
                          ``transform_and_rollup`` dispatch, zero syncs
                          until ``FactBlock.to_host()`` at the load
                          boundary). The worker step drops from 3
                          host↔device round trips to 1; the serving fold
                          keeps its single (now segment-compacted) trip in
                          the maintenance stage, off the worker's hot path.
  * ``sustained``       — paired, interleaved A/B single-worker
                          sustained-load cycles over the steelworks
                          workload (same feeder/closed loop as
                          ``benchmarks.sustained_load``):
                            A = the pre-PR coalesced sequential round loop
                                (ONE dispatch per step, immediate host
                                sync, no fused rollup — reproduced
                                verbatim),
                            B = the device-plane loop: fetch N+1 and
                                dispatch it while step N's block is still
                                computing / copying D2H, then materialize
                                N at its load boundary (the same software
                                pipeline the concurrent runtime's
                                transform->load stages execute on threads),
                            C = the SHIPPED single-worker
                                ``ConcurrentCluster`` with the serving
                                engine attached (same views, compacted
                                folds in the maintenance stage) — the
                                headline arm.
                          Headline = median of per-cycle B/A ratios
                          (paired/interleaved — the only trustworthy
                          estimator on the noisy 2-core reference host,
                          see docs/BENCHMARKS.md).
  * ``fold_compaction`` — paired timings of the segment-compacted fold vs
                          a verbatim reproduction of the uncompacted
                          halving tree on sparse deltas, plus the bitwise
                          equality check that makes compaction legal.

    PYTHONPATH=src python -m benchmarks.dispatch_overhead [--smoke]
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import threading
import time
from typing import Dict, Optional

# pin XLA intra-op parallelism BEFORE jax initializes (one core per worker
# thread — identical accounting to benchmarks.sustained_load)
_PIN = "--xla_cpu_multi_thread_eigen=false intra_op_parallelism_threads=1"
if "xla_cpu_multi_thread_eigen" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "") + " " + _PIN).strip()

import numpy as np

from benchmarks.sustained_load import (Workload, feed_waves, prewarm,
                                       seed_source)
from repro.core import DODETLPipeline, RecordBatch
from repro.core.backend import get_backend
from repro.core.cache import InMemoryTable


# =========================================================== 1. round trips
def _bench_tables(rng, n_units: int, n_prod: int):
    eq = InMemoryTable(max(256, 4 * n_units))
    eqp = np.zeros((n_units, 8), np.float32)
    eqp[:, 1] = np.arange(n_units)
    eqp[:, 4] = 100.0
    eqp[:, 5] = (rng.random(n_units) > 0.3).astype(np.float32)
    eqp[:, 6] = 5.0 + rng.random(n_units).astype(np.float32)
    eqp[:, 7] = 50.0
    eq.upsert(np.arange(n_units), eqp, np.arange(n_units, dtype=np.int64))
    qu = InMemoryTable(1 << max(10, (4 * n_prod).bit_length()))
    qp = np.zeros((n_prod, 8), np.float32)
    qp[:, 3] = np.arange(n_prod)
    qp[:, 4] = rng.integers(0, 3, n_prod)
    qu.upsert(np.arange(n_prod), qp, np.arange(n_prod, dtype=np.int64))
    return eq, qu


def measure_round_trips(backend: str = "jax", n: int = 2048,
                        n_units: int = 20) -> Dict:
    """Count dispatches + blocking host syncs over one BI step (one fact
    block through transform -> per-unit rollup -> serving fold) for the
    pre-PR op sequence vs the device plane."""
    rng = np.random.default_rng(0)
    eq, qu = _bench_tables(rng, n_units, 4 * n)
    prod = np.zeros((n, 8), np.float32)
    prod[:, 0] = rng.integers(0, 4 * n, n)
    prod[:, 1] = rng.integers(0, n_units, n)
    prod[:, 3] = rng.uniform(0, 50, n)
    prod[:, 4] = prod[:, 3] + rng.uniform(1, 30, n)
    prod[:, 5] = rng.uniform(1, 100, n)
    be = get_backend(backend)

    # warm every jit outside the counted window
    be.transform(prod, eq, qu)
    be.transform_and_rollup(prod, eq, qu, n_units=n_units).to_host()
    facts_w, found_w = be.transform(prod, eq, qu)
    be.segment_reduce(facts_w[found_w], n_units)
    be.fold_segments(facts_w[found_w][:, 0].astype(np.int64),
                     facts_w[found_w][:, 3:7], n_units)

    # ---- pre-PR: three separate ops, each ferrying the block H->D->H
    be.reset_stats()
    facts, found = be.transform(prod, eq, qu)            # trip 1: transform
    good = facts[found]
    be.segment_reduce(good, n_units)                     # trip 2: rollup
    be.fold_segments(good[:, 0].astype(np.int64),        # trip 3: view fold
                     good[:, 3:7], n_units)
    pre = {"dispatches": be.op_dispatches, "host_syncs": be.host_syncs}

    # ---- device plane: ONE fused dispatch; the block stays on device
    # until the load boundary (to_host = the step's single round trip);
    # the rollup rides the same dispatch and the same sync
    be.reset_stats()
    block = be.transform_and_rollup(prod, eq, qu,
                                    n_units=n_units).start_host_copy()
    before_load = {"dispatches": be.op_dispatches,
                   "host_syncs": be.host_syncs}
    h_facts, h_found = block.to_host()
    rollup = block.rollup_host()
    post = {"dispatches": be.op_dispatches, "host_syncs": be.host_syncs}

    # the serving fold is no longer on the worker step: it runs in the
    # maintenance stage, segment-compacted (counted separately)
    be.reset_stats()
    good2 = h_facts[h_found]
    be.fold_segments(good2[:, 0].astype(np.int64), good2[:, 3:7], n_units)
    fold = {"dispatches": be.op_dispatches, "host_syncs": be.host_syncs}

    np.testing.assert_allclose(rollup, be.segment_reduce(good2, n_units),
                               rtol=1e-5, atol=1e-4)
    return {
        "backend": backend, "block_rows": n, "n_units": n_units,
        "pre_pr_worker_step": pre,
        "device_plane_before_load": before_load,
        "device_plane_worker_step": post,
        "serving_fold_per_delta": fold,
        "round_trips_per_worker_step": {
            "pre": pre["host_syncs"], "post": post["host_syncs"]},
        "note": ("host_syncs = blocking device->host materializations per "
                 "worker step (transform + per-unit rollup + load "
                 "boundary). The serving-layer fold keeps one compacted "
                 "trip per delta in its own maintenance stage."),
    }


# ======================================================== 2. sustained A/B
# Both arms run the full single-worker BI-SERVING step the paper's
# deployment needs (and examples/steelworks_etl.py runs): transform the
# fetched block, load it, maintain the per-unit KPI aggregate, fold the
# delta into every steelworks report view. The pre-PR op sequence ferries
# the block host<->device three times per step (transform sync, separate
# segment_reduce, full-width view folds); the device plane does ONE fused
# dispatch + ONE sync at the load boundary and folds compacted.

def _make_views(wl: Workload):
    """The steelworks report suite plus a long-horizon dashboard view:
    per-shift production-rate windows over a 288-window ring (~2 weeks of
    4000-tick shifts). The workload's event time advances wave over wave,
    so each delta lands in the ~20 newest windows of 288 — long-horizon
    windowed views are SPARSE per delta by construction, which is what
    segment compaction exploits: the pre-PR fold ran the halving tree
    over all 288 columns for every delta."""
    import dataclasses as _dc

    from repro.serving import production_rate_windows, steelworks_views
    views = list(steelworks_views(wl.n_partitions))
    views.append(_dc.replace(
        production_rate_windows(n_windows=288, window_len=4000.0),
        name="production_rate_shift_ring"))
    return tuple(views)


def _fold_into(states, views, good, fold_fn):
    from repro.core.backend import combine_fold
    for spec in views:
        agg = fold_fn(spec.segments(good), spec.values(good),
                      spec.n_segments)
        states[spec.name] = combine_fold(states[spec.name], agg)


def _fresh_states(views):
    from repro.core.backend import empty_fold_state
    return {s.name: empty_fold_state(s.n_segments, s.n_lanes)
            for s in views}


def _warm_fold_shapes(views, be) -> None:
    """Compile the fold buckets the measured loops hit (jit caches are
    process-global, so this runs once): every row bucket at full
    coverage — compacted op AND uncompacted reproduction — plus the
    sparse width ladder at the big buckets steady-state deltas produce.
    Rare unlisted shapes (tiny retry sweeps) compile small, cheap trees
    on first hit in either arm."""
    from repro.core.backend import FOLD_BLOCK
    for spec in {(s.n_segments, s.n_lanes) for s in views}:
        S, L = spec
        m = 8
        while m <= FOLD_BLOCK:
            vals = np.zeros((m, L), np.float32)
            be.fold_segments(np.arange(m, dtype=np.int64) % S, vals, S)
            _uncompacted_fold_jax(np.arange(m, dtype=np.int64) % S, vals, S)
            m *= 2
        for m in (FOLD_BLOCK // 2, FOLD_BLOCK):
            vals = np.zeros((m, L), np.float32)
            width = 8
            while width < S:
                be.fold_segments(np.arange(m, dtype=np.int64) % width,
                                 vals, S)
                width *= 2


def _pre_pr_sequential(wl: Workload, views) -> Dict:
    """THE reference of this PR: the pre-PR coalesced single-worker round
    loop — one transform dispatch per step with an IMMEDIATE blocking host
    sync (`sequential.1_coalesced` of benchmarks.sustained_load as of the
    previous PR), plus the pre-PR BI epilogue per step: a separate
    ``segment_reduce`` dispatch for the per-unit KPI aggregate and
    full-width (uncompacted) view folds of the loaded delta."""
    cfg, src, sampler = seed_source(wl)
    pipe = DODETLPipeline(cfg, src, n_workers=1, join_depth=wl.join_depth)
    for w in pipe.workers:          # pre-PR dispatch: facts only, no fused
        w.transformer.n_units = None    # rollup riding the kernel
    prewarm(pipe, wl)
    be = pipe.backend
    cap = wl.cap_for(1)
    w = pipe.workers[0]
    tr = w.transformer
    states = _fresh_states(views)
    kpi_agg = np.zeros((cfg.n_business_keys, 5), np.float32)
    feeder = threading.Thread(target=feed_waves, args=(sampler, src, wl))
    total, stalls = 0, 0
    t0 = time.perf_counter()
    feeder.start()
    while total < wl.total_ops and stalls < 200:
        pipe.extract()
        w.pump_master(pipe.master_topic_map["equipment"], w.equipment)
        w.pump_master(pipe.master_topic_map["quality"], w.quality)
        stepped = 0
        for topic in pipe.operational_topics:
            batch, counts = pipe.queue.consume_many(
                w.group, topic, w.partitions, cap)
            for p, c in counts.items():
                pipe.queue.commit(w.group, topic, p, c)
            block, merged = tr.process_block(batch)
            if block is None:
                continue
            good, _ = tr.finish(block, merged)   # trip 1: immediate sync
            w.warehouse.load_partitioned(good, cfg.n_partitions)
            if len(good):
                kpi_agg += be.segment_reduce(good,   # trip 2: rollup
                                             cfg.n_business_keys)
                _fold_into(states, views, good,      # trip 3: view folds
                           _uncompacted_fold_jax)
            stepped += len(good)
        total += stepped
        stalls = stalls + 1 if stepped == 0 else 0
    wall = time.perf_counter() - t0
    feeder.join()
    return {"records": total, "wall_s": round(wall, 4),
            "records_s": round(total / wall) if wall else 0,
            "kpi_rows": int(kpi_agg[:, 4].sum()),
            "view_rows": int(states[views[0].name][:, 0].sum())}


def _device_plane_sequential(wl: Workload, views) -> Dict:
    """The device-plane single-worker loop: ONE fused transform+rollup
    dispatch per step, block handed forward DEVICE-RESIDENT with its D2H
    copy enqueued asynchronously; the PREVIOUS step's block materializes
    at its load boundary — so device compute + copy overlap the load-side
    host work (the same overlap the concurrent runtime's transform->load
    stages get from threads) — and the view folds run segment-compacted."""
    cfg, src, sampler = seed_source(wl)
    pipe = DODETLPipeline(cfg, src, n_workers=1, join_depth=wl.join_depth)
    prewarm(pipe, wl)
    be = pipe.backend
    cap = wl.cap_for(1)
    w = pipe.workers[0]
    tr = w.transformer
    states = _fresh_states(views)
    feeder = threading.Thread(target=feed_waves, args=(sampler, src, wl))
    total, stalls = 0, 0
    pending = None                  # (block, merged batch) of step N-1

    def retire(p):
        block, merged = p
        good, _ = tr.finish(block, merged)      # the ONE sync, at load
        w.warehouse.load_partitioned(good, cfg.n_partitions,
                                     rollup=block.rollup_host())
        if len(good):
            _fold_into(states, views, good, be.fold_segments)
        return len(good)

    t0 = time.perf_counter()
    feeder.start()
    while total < wl.total_ops and stalls < 200:
        pipe.extract()
        w.pump_master(pipe.master_topic_map["equipment"], w.equipment)
        w.pump_master(pipe.master_topic_map["quality"], w.quality)
        stepped = 0
        for topic in pipe.operational_topics:
            batch, counts = pipe.queue.consume_many(
                w.group, topic, w.partitions, cap)
            for p, c in counts.items():
                pipe.queue.commit(w.group, topic, p, c)
            block, merged = tr.process_block(batch)
            if block is not None:
                block.start_host_copy()         # D2H rides the compute
            if pending is not None:
                stepped += retire(pending)      # overlaps block's compute
                pending = None
            if block is not None:
                pending = (block, merged)
        if pending is not None and stepped == 0:
            stepped += retire(pending)          # drain when idle
            pending = None
        total += stepped
        stalls = stalls + 1 if stepped == 0 else 0
    if pending is not None:
        total += retire(pending)
    wall = time.perf_counter() - t0
    feeder.join()
    running = w.warehouse.kpi_running()
    return {"records": total, "wall_s": round(wall, 4),
            "records_s": round(total / wall) if wall else 0,
            "kpi_rows": int(running[:, 4].sum())
            if running is not None else -1,
            "view_rows": int(states[views[0].name][:, 0].sum())}


def _concurrent_serving(wl: Workload, views) -> Dict:
    """The SHIPPED device-plane deployment, single worker: the
    ``ConcurrentCluster`` hot path (fused transform+rollup dispatch in the
    transform stage, device block handed to the load stage, one sync at
    the load boundary) with the ``MaterializedViewEngine`` attached — the
    same steelworks views, folded segment-compacted by the maintenance
    stage. The wall clock runs until the stream is drained AND the fold
    backlog is empty, so the serving work is fully charged."""
    from repro.runtime.cluster import ConcurrentCluster
    from repro.serving import MaterializedViewEngine
    cfg, src, sampler = seed_source(wl)
    pipe = DODETLPipeline(cfg, src, n_workers=1, join_depth=wl.join_depth)
    prewarm(pipe, wl)
    engine = MaterializedViewEngine(views, backend=wl.backend)
    cluster = ConcurrentCluster(pipe, max_records_per_partition=wl.cap_for(1),
                                serving=engine)
    feeder = threading.Thread(target=feed_waves, args=(sampler, src, wl))
    t0 = time.perf_counter()
    cluster.start()
    feeder.start()
    feeder.join()
    done = cluster.run_until_idle(timeout=600.0)
    deadline = time.perf_counter() + 60.0
    while engine.pending() and time.perf_counter() < deadline:
        time.sleep(0.001)            # charge the fold backlog to the wall
    wall = time.perf_counter() - t0
    cluster.stop_all()
    snap = engine.snapshot()
    return {"records": done, "wall_s": round(wall, 4),
            "records_s": round(done / wall) if wall else 0,
            "complete": done == wl.total_ops,
            "rows_folded": snap.rows_folded,
            "view_rows": int(snap.states[views[0].name].count.sum())}


def measure_sustained(wl: Workload, repeats: int) -> Dict:
    """Interleaved paired cycles: (A, B, C) adjacent in time per cycle,
    every arm doing the full BI-serving work (transform + per-unit KPI
    aggregate + view folds). Headline = median per-cycle C/A ratio — the
    shipped device-plane runtime against the pre-PR op sequence; B/A
    isolates the device plane in a single thread."""
    views = _make_views(wl)
    _warm_fold_shapes(views, get_backend(wl.backend))
    cycles = []
    for _ in range(repeats):
        a = _pre_pr_sequential(wl, views)
        b = _device_plane_sequential(wl, views)
        c = _concurrent_serving(wl, views)
        cycles.append({
            "pre_pr_coalesced": a, "device_plane": b,
            "concurrent_serving_1w": c,
            "device_plane_vs_pre_pr":
                round(b["records_s"] / max(a["records_s"], 1), 3),
            "concurrent_serving_vs_pre_pr":
                round(c["records_s"] / max(a["records_s"], 1), 3),
        })

    def med(key):
        rs = sorted(cy[key] for cy in cycles)
        return rs[len(rs) // 2]

    return {
        "workload": {**dataclasses.asdict(wl), "total_ops": wl.total_ops},
        "cycles": cycles,
        "paired_median_device_plane_vs_pre_pr":
            med("device_plane_vs_pre_pr"),
        "paired_median_concurrent_serving_vs_pre_pr":
            med("concurrent_serving_vs_pre_pr"),
        "note": ("single-worker BI-serving pipeline (transform + per-unit "
                 "KPI aggregate + steelworks view folds of every delta). "
                 "A = pre-PR op sequence (immediate sync, separate "
                 "segment_reduce dispatch, full-width folds — 3 block "
                 "round trips/step), B = device-plane loop in one thread "
                 "(one fused dispatch, one load-boundary sync, compacted "
                 "folds), C = the SHIPPED single-worker ConcurrentCluster "
                 "with the serving engine attached (same folds in the "
                 "maintenance stage; wall includes draining the fold "
                 "backlog). Interleaved A,B,C per cycle; medians of "
                 "paired per-cycle ratios"),
    }


# ====================================================== 3. fold compaction
def _uncompacted_fold_jax(seg, vals, n_segments):
    """Verbatim reproduction of the pre-compaction fold driver: the jitted
    halving tree over the FULL [block, n_segments, lanes] range."""
    from repro.core.backend import (FOLD_BLOCK, _fold_tree_jnp, combine_fold,
                                    empty_fold_state)
    import jax.numpy as jnp
    seg = np.asarray(seg, np.int64)
    vals = np.asarray(vals, np.float32)
    n, L = vals.shape
    out = empty_fold_state(n_segments, L)
    for lo in range(0, n, FOLD_BLOCK):
        s = seg[lo:lo + FOLD_BLOCK]
        v = vals[lo:lo + FOLD_BLOCK]
        m = len(s)
        bucket = max(8, 1 << (m - 1).bit_length())
        if bucket != m:
            s = np.concatenate([s, np.full(bucket - m, -1, np.int64)])
            v = np.concatenate([v, np.zeros((bucket - m, L), np.float32)])
        out = combine_fold(out, np.asarray(_fold_tree_jnp(
            jnp.asarray(s, jnp.int32), jnp.asarray(v), n_segments)))
    return out


def measure_fold_compaction(repeats: int = 5, n_rows: int = 4096,
                            n_segments: int = 256, lanes: int = 4) -> Dict:
    """Sparse deltas (the serving layer's common case: one worker's delta
    touches its own partitions' segments only) folded compacted vs the
    uncompacted reproduction — paired per-repeat ratios + bitwise check."""
    be = get_backend("jax")
    rng = np.random.default_rng(1)
    out = {"n_rows": n_rows, "n_segments": n_segments, "lanes": lanes,
           "sparsity": {}}
    for n_active in (1, 2, 8, n_segments):
        live = rng.choice(n_segments, n_active, replace=False)
        seg = rng.choice(live, n_rows)
        vals = rng.normal(size=(n_rows, lanes)).astype(np.float32)
        # warm both jit shapes, verify bitwise equality once
        compacted = be.fold_segments(seg, vals, n_segments)
        reference = _uncompacted_fold_jax(seg, vals, n_segments)
        bitwise = compacted.tobytes() == reference.tobytes()
        ratios = []
        for _ in range(repeats):              # paired, interleaved
            t0 = time.perf_counter()
            _uncompacted_fold_jax(seg, vals, n_segments)
            t_un = time.perf_counter() - t0
            t0 = time.perf_counter()
            be.fold_segments(seg, vals, n_segments)
            t_co = time.perf_counter() - t0
            ratios.append(t_un / max(t_co, 1e-9))
        ratios.sort()
        out["sparsity"][str(n_active)] = {
            "bitwise_equal": bool(bitwise),
            "median_paired_speedup": round(ratios[len(ratios) // 2], 2),
            "paired_speedups": [round(r, 2) for r in ratios],
        }
    return out


# ================================================================== driver
def summary(quick: bool = True) -> Dict:
    """Fast counter summary for benchmarks.run (no sustained sweep)."""
    rt = measure_round_trips(n=1024 if quick else 2048)
    fold = measure_fold_compaction(repeats=3 if quick else 5,
                                   n_rows=2048 if quick else 4096)
    sparse = fold["sparsity"]["2"]
    return {
        "round_trips_pre": rt["round_trips_per_worker_step"]["pre"],
        "round_trips_post": rt["round_trips_per_worker_step"]["post"],
        "fold_compaction_speedup_2_of_256":
            sparse["median_paired_speedup"],
        "fold_bitwise_equal": sparse["bitwise_equal"],
    }


def main() -> None:
    import sys
    sys.setswitchinterval(0.02)     # same rationale as sustained_load
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny workload (CI harness check)")
    ap.add_argument("--repeats", type=int, default=None)
    ap.add_argument("--join-depth", type=int, default=None)
    ap.add_argument("--dispatch", type=int, default=8192)
    ap.add_argument("--backend", default="jax")
    ap.add_argument("--out", default="BENCH_dispatch.json")
    args = ap.parse_known_args()[0]

    if args.smoke:
        wl = Workload(n_base=800, waves=2, chunk=800, n_partitions=8,
                      join_depth=args.join_depth or 2,
                      backend=args.backend, dispatch=args.dispatch)
        repeats = args.repeats or 1
    else:
        # join_depth 2 ~ the paper's SIMPLE process-specific model (its
        # default deployment): the BI epilogue this suite measures is a
        # realistic fraction of the step. --join-depth 8/32 replays the
        # normalized ISA-95 cost profile where the transform dominates.
        # Shorter runs (60 waves) x more cycles beat the host's
        # seconds-timescale drift better than few long runs.
        wl = Workload(waves=60, join_depth=args.join_depth or 2,
                      backend=args.backend, dispatch=args.dispatch)
        repeats = args.repeats or 9

    results = {
        "host": {"cores": os.cpu_count()},
        "round_trips": measure_round_trips(backend=args.backend),
        "fold_compaction": measure_fold_compaction(
            repeats=3 if args.smoke else 7),
        "sustained": measure_sustained(wl, repeats),
    }
    rt = results["round_trips"]["round_trips_per_worker_step"]
    print(f"round trips per worker step: {rt['pre']} -> {rt['post']}")
    print(f"fold compaction: {results['fold_compaction']['sparsity']}")
    su = results["sustained"]
    print(f"sustained paired medians vs pre-PR coalesced loop: "
          f"device-plane {su['paired_median_device_plane_vs_pre_pr']}x, "
          f"shipped concurrent+serving "
          f"{su['paired_median_concurrent_serving_vs_pre_pr']}x")
    with open(args.out, "w") as f:
        json.dump(results, f, indent=2)
    print(f"wrote {args.out}")


if __name__ == "__main__":
    from repro.core.device import enable_compile_cache
    enable_compile_cache()
    main()
