"""Report-serving benchmark: the read-side claim suite.

Five measurements, written to ``BENCH_views.json``:

* ``query_latency`` — incremental-view report queries
  (``ReportServer.kpi_rollup``, O(n_units) reads of folded state) vs the
  ad-hoc full-rescan path (``Warehouse.kpi_rollup``, O(fact-table)
  concat + segmented reduce) across fact-table sizes. Paired/interleaved:
  each repeat times rescan and view back-to-back and the headline speedup
  is the **median of per-repeat ratios** (the noisy-2-core-host
  methodology of docs/BENCHMARKS.md). Parity is asserted every repeat —
  the view must answer byte-equal counts and ~1e-4-close sums.

* ``concurrency`` — sustained query throughput while a writer keeps
  loading + folding: N reader threads issue snapshot-pinned queries;
  reports qps and per-query p50/p95, and asserts epochs observed by every
  reader are monotone (no torn reads under write pressure).

* ``staleness_e2e`` — end-to-end report staleness (CDC append ->
  visible-in-query) under sustained load on a live ``ConcurrentCluster``
  with the serving stage attached, next to the pipeline's load-freshness
  percentiles. The headline is ``staleness_p95 / freshness_p95`` — how
  much the serving hop adds on top of the write path (acceptance: <= 2x).

* ``batched`` — the batched query plane vs the per-query dispatch loop:
  one compiled ``QueryPlan`` of B heterogeneous queries (per-unit OEE
  point queries + view reads + top-k + windowed rates + shift/rollup)
  executed in one vectorized dispatch per view, against B sequential
  ``ReportSnapshot`` calls. Paired per repeat with a FRESH epoch before
  each side so neither inherits the other's per-epoch memos; parity is
  byte-asserted on a shared epoch each repeat. Headlines: columnar
  effective qps and the median paired speedup at each batch size
  (acceptance: >= 5x at B >= 1024).

* ``scan_fold`` — the associative-scan windowed fold: read side, ONE
  ``prefix_fold`` scan answering all S cumulative-window prefixes vs the
  bitwise-identical per-window tree recompute (``prefix_fold_reference``)
  — the S >= 128 win; write side, ``fold_segments_scan`` vs the unrolled
  halving tree on one delta — measured honestly (the scan LOSES on CPU
  hosts; documented, tree stays the default). Bitwise equality asserted
  on both sides every repeat.

    PYTHONPATH=src python -m benchmarks.report_serving [--smoke]
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import threading
import time
from typing import Dict, List, Sequence

import numpy as np

# reuses the sustained-load workload machinery AND its XLA single-thread
# pin (set at that module's import, before jax initializes)
from benchmarks.sustained_load import (Workload, feed_waves, make_config,
                                       prewarm)
from repro.core.cdc import SourceDatabase
from repro.data.sampler import (SamplerConfig, SteelworksSampler,
                                synthetic_facts)
from repro.core import DODETLPipeline, StarSchemaWarehouse, percentiles_ms
from repro.core.backend import get_backend, prefix_fold_reference
from repro.runtime.cluster import ConcurrentCluster
from repro.serving import (MaterializedViewEngine, ReportQuery, ReportServer,
                           compile_queries, production_rate_windows,
                           steelworks_views)

N_UNITS = 20


def _median(xs: Sequence[float]) -> float:
    return float(sorted(xs)[len(xs) // 2])


def _loaded_server(n_rows: int, backend: str, chunk: int = 8192):
    rng = np.random.default_rng(n_rows)
    wh = StarSchemaWarehouse(backend=get_backend(backend))
    engine = wh.attach_serving(
        MaterializedViewEngine(steelworks_views(N_UNITS), backend=backend))
    for lo in range(0, n_rows, chunk):
        wh.load_partitioned(
            synthetic_facts(rng, min(chunk, n_rows - lo), N_UNITS), N_UNITS)
    engine.fold_pending()
    return wh, engine, ReportServer(engine)


# ------------------------------------------------------------- query latency
def bench_query_latency(sizes: Sequence[int], reps: int,
                        backend: str = "jax") -> Dict:
    out: Dict[str, object] = {"sizes": list(sizes), "backend": backend,
                              "per_size": {}}
    for n_rows in sizes:
        wh, engine, server = _loaded_server(n_rows, backend)
        wh.kpi_rollup(N_UNITS)          # jit warm-up outside the window
        server.kpi_rollup()
        rescan_ms, view_ms, ratios = [], [], []
        parity_ok = True
        for _ in range(reps):           # interleaved, paired per repeat
            t0 = time.perf_counter()
            scan = wh.kpi_rollup(N_UNITS)
            t1 = time.perf_counter()
            # view queries are microseconds: time a burst of 100
            for _ in range(100):
                view = server.kpi_rollup()
            t2 = time.perf_counter()
            r_ms = (t1 - t0) * 1e3
            v_ms = (t2 - t1) * 1e3 / 100
            rescan_ms.append(round(r_ms, 4))
            view_ms.append(round(v_ms, 5))
            ratios.append(r_ms / max(v_ms, 1e-6))
            parity_ok &= bool(
                np.array_equal(view[:, 4], scan[:, 4])
                and np.allclose(view, scan, rtol=1e-4, atol=1e-4))
        out["per_size"][str(n_rows)] = {
            "rows": n_rows,
            "rescan_ms_runs": rescan_ms,
            "view_query_ms_runs": view_ms,
            "rescan_ms": _median(rescan_ms),
            "view_query_ms": _median(view_ms),
            "paired_speedups": [round(r, 1) for r in ratios],
            "speedup_view_vs_rescan": round(_median(ratios), 1),
            "parity_ok": parity_ok,
        }
    largest = out["per_size"][str(max(sizes))]
    out["speedup_at_largest"] = largest["speedup_view_vs_rescan"]
    out["parity_ok"] = all(v["parity_ok"]
                           for v in out["per_size"].values())
    return out


# --------------------------------------------------------------- concurrency
def bench_concurrency(n_rows: int, thread_counts: Sequence[int],
                      queries_per_thread: int,
                      backend: str = "jax") -> Dict:
    out: Dict[str, object] = {"rows_preloaded": n_rows,
                              "queries_per_thread": queries_per_thread,
                              "per_threads": {}}
    for n_threads in thread_counts:
        wh, engine, server = _loaded_server(n_rows, backend)
        engine.start()                  # maintenance folds while we query
        stop = threading.Event()

        def writer():
            wrng = np.random.default_rng(1)
            while not stop.is_set():
                wh.load_partitioned(synthetic_facts(wrng, 2048, N_UNITS),
                                    N_UNITS)
                time.sleep(0.001)

        lat: List[np.ndarray] = [None] * n_threads
        torn = [False] * n_threads

        def reader(i: int):
            samples = np.zeros(queries_per_thread)
            last_epoch, last_count = -1, -1.0
            for q in range(queries_per_thread):
                t0 = time.perf_counter()
                snap = server.snapshot()
                roll = snap.kpi_rollup()
                samples[q] = time.perf_counter() - t0
                count = float(roll[:, 4].sum())
                if snap.epoch < last_epoch or count < last_count:
                    torn[i] = True
                last_epoch, last_count = snap.epoch, count
            lat[i] = samples

        wthread = threading.Thread(target=writer)
        readers = [threading.Thread(target=reader, args=(i,))
                   for i in range(n_threads)]
        wthread.start()
        t0 = time.perf_counter()
        for r in readers:
            r.start()
        for r in readers:
            r.join()
        wall = time.perf_counter() - t0
        stop.set()
        wthread.join()
        engine.stop()
        total_q = n_threads * queries_per_thread
        res = {"queries": total_q, "wall_s": round(wall, 4),
               "qps": round(total_q / wall) if wall else 0,
               "epochs_final": engine.snapshot().epoch,
               "monotonic": not any(torn)}
        res.update({f"query_{k}": v for k, v in
                    percentiles_ms(np.concatenate(lat)).items()})
        out["per_threads"][str(n_threads)] = res
    return out


# ------------------------------------------------------------- staleness e2e
def bench_staleness(wl: Workload, n_workers: int = 2) -> Dict:
    # unlike the sustained-load harness, seed NOTHING before the cluster
    # starts: every CDC append lands while the pipeline is live, so the
    # freshness/staleness stamps measure the running system, not a
    # pre-start backlog aging through jit warm-up
    cfg = make_config(wl)
    src = SourceDatabase()
    sampler = SteelworksSampler(cfg, SamplerConfig(
        records_per_table=wl.n_base, n_equipment=wl.n_partitions,
        late_master_frac=wl.late_frac))
    pipe = DODETLPipeline(cfg, src, n_workers=n_workers,
                          join_depth=wl.join_depth)
    prewarm(pipe, wl)
    engine = MaterializedViewEngine(
        steelworks_views(wl.n_partitions), backend=wl.backend)
    engine.prewarm()       # fold buckets compile outside the window
    cluster = ConcurrentCluster(
        pipe, max_records_per_partition=wl.cap_for(n_workers),
        serving=engine)

    def feed():
        sampler.generate(src)           # masters + base, cluster already up
        feed_waves(sampler, src, wl)

    feeder = threading.Thread(target=feed)
    t0 = time.perf_counter()
    cluster.start()
    feeder.start()
    feeder.join()
    done = cluster.run_until_idle(timeout=600.0)
    # wait for the maintenance stage to drain the delta backlog so the
    # staleness samples cover every record (stop_all also folds the rest)
    deadline = time.time() + 30.0
    while engine.pending() and time.time() < deadline:
        time.sleep(0.005)
    wall = time.perf_counter() - t0
    freshness = cluster.freshness()
    cluster.stop_all()
    staleness = engine.staleness()
    ratio = (round(staleness["p95_ms"] / freshness["p95_ms"], 2)
             if freshness["p95_ms"] else None)
    return {"records": done, "complete": done == wl.total_ops,
            "wall_s": round(wall, 4),
            "records_s": round(done / wall) if wall else 0,
            "n_workers": n_workers,
            "freshness": freshness, "staleness": staleness,
            "staleness_p95_over_freshness_p95": ratio,
            "epoch": engine.snapshot().epoch,
            "rows_folded": engine.snapshot().rows_folded}


# ------------------------------------------------------------ batched plane
def _batch_mix(batch: int) -> List[ReportQuery]:
    """Deterministic heterogeneous mix: 75% per-unit OEE point queries
    (the dashboard fan-out shape), the rest spread over view reads,
    top-k downtime, windowed rates, shift reports and cumulative curves."""
    qs: List[ReportQuery] = []
    for i in range(batch):
        j = i % 16
        if j < 12:
            qs.append(ReportQuery("oee", unit=i % N_UNITS))
        elif j == 12:
            qs.append(ReportQuery(
                "view", view=("oee_by_equipment" if i % 32 < 16
                              else "production_rate_windows")))
        elif j == 13:
            qs.append(ReportQuery("top_downtime", k=5))
        elif j == 14:
            qs.append(ReportQuery("production_rate"))
        elif i % 32 < 16:
            qs.append(ReportQuery("shift_report"))
        else:
            qs.append(ReportQuery("production_curve"))
    return qs


def _run_loop(rs, queries: Sequence[ReportQuery]) -> list:
    """The status-quo path: one Python-dispatched snapshot read per query."""
    out = []
    for q in queries:
        k = q.kind
        if k == "oee":
            out.append(rs.oee(q.unit))
        elif k == "view":
            out.append(rs.query(q.view))
        elif k == "top_downtime":
            out.append(rs.top_downtime(q.k))
        elif k == "production_rate":
            out.append(rs.production_rate())
        elif k == "shift_report":
            out.append(rs.shift_report())
        elif k == "production_curve":
            out.append(rs.production_curve())
        else:
            out.append(rs.kpi_rollup())
    return out


def _answers_equal(batched, loop_answer) -> bool:
    if isinstance(loop_answer, np.ndarray):          # kpi_rollup payload
        return batched.data["kpi_rollup"].tobytes() == loop_answer.tobytes()
    for key, want in loop_answer.data.items():
        got = batched.data[key]
        if isinstance(want, np.ndarray):
            if np.asarray(got).tobytes() != want.tobytes():
                return False
        elif isinstance(want, float):
            if got != want and not (np.isnan(got) and np.isnan(want)):
                return False
        elif got != want:
            return False
    return True


def bench_batched(n_rows: int, batch_sizes: Sequence[int], reps: int,
                  backend: str = "jax") -> Dict:
    """Compiled-plan batch execution vs the per-query loop. Each repeat
    folds a fresh epoch before EACH side, so both run with cold per-epoch
    memos (neither inherits the other's shared derivations); the headline
    is the median of per-repeat paired ratios. Byte parity between both
    paths is asserted on a shared epoch once per batch size."""
    wh, engine, server = _loaded_server(n_rows, backend)
    engine.prewarm_read()
    rng = np.random.default_rng(99)

    def advance_epoch():
        wh.load_partitioned(synthetic_facts(rng, 256, N_UNITS), N_UNITS)
        engine.fold_pending()

    out: Dict[str, object] = {"rows_preloaded": n_rows, "backend": backend,
                              "mix": "75% point OEE + shared reports",
                              "per_batch": {}}
    for batch in batch_sizes:
        queries = _batch_mix(batch)
        t0 = time.perf_counter()
        plan = compile_queries(queries)
        compile_ms = (time.perf_counter() - t0) * 1e3
        # parity on ONE shared epoch (untimed), then warm both paths
        advance_epoch()
        rs = server.snapshot()
        parity_ok = all(_answers_equal(a, b) for a, b in
                        zip(plan.execute(rs).reports(),
                            _run_loop(rs, queries)))
        exec_ms, rep_ms, loop_ms, ratios = [], [], [], []
        epochs: List[int] = []
        for _ in range(reps):
            advance_epoch()
            rs_b = server.snapshot()
            t0 = time.perf_counter()
            res = plan.execute(rs_b)             # columnar answer
            t1 = time.perf_counter()
            res.reports()                        # per-query materialization
            t2 = time.perf_counter()
            advance_epoch()
            rs_l = server.snapshot()
            t3 = time.perf_counter()
            _run_loop(rs_l, queries)
            t4 = time.perf_counter()
            e, r, l = [(b - a) * 1e3 for a, b in
                       ((t0, t1), (t1, t2), (t3, t4))]
            exec_ms.append(round(e, 4))
            rep_ms.append(round(r, 4))
            loop_ms.append(round(l, 4))
            ratios.append(l / max(e, 1e-9))
            epochs.append(res.epoch)
        e_med, r_med, l_med = (_median(exec_ms), _median(rep_ms),
                               _median(loop_ms))
        out["per_batch"][str(batch)] = {
            "batch": batch,
            "plan_compile_ms": round(compile_ms, 4),
            "exec_ms_runs": exec_ms, "loop_ms_runs": loop_ms,
            "exec_ms": e_med, "reports_ms": r_med, "loop_ms": l_med,
            "qps_columnar": round(batch / (e_med * 1e-3)),
            "qps_materialized": round(batch / ((e_med + r_med) * 1e-3)),
            "qps_loop": round(batch / (l_med * 1e-3)),
            "paired_speedups": [round(x, 2) for x in ratios],
            "speedup_vs_loop": round(_median(ratios), 2),
            "parity_ok": bool(parity_ok),
            "epochs_monotonic": epochs == sorted(epochs)
            and len(set(epochs)) == len(epochs),
        }
    largest = out["per_batch"][str(max(batch_sizes))]
    out["speedup_at_largest"] = largest["speedup_vs_loop"]
    out["qps_at_largest"] = largest["qps_columnar"]
    out["parity_ok"] = all(v["parity_ok"]
                           for v in out["per_batch"].values())
    return out


# ---------------------------------------------------------------- scan folds
def bench_scan_fold(window_sizes: Sequence[int], reps: int,
                    backend: str = "jax", delta_rows: int = 4096) -> Dict:
    """Associative-scan windowed folds, both sides of the story.

    READ side (the win): ONE ``prefix_fold`` scan answers all S
    cumulative-window prefixes vs recomputing each window's prefix with
    the bitwise-identical tree chaining (``prefix_fold_reference``) — the
    O(S log S) vs O(S^2) gap that opens decisively by S >= 128.

    WRITE side (the honest negative): ``fold_segments_scan`` vs the
    unrolled halving tree on the same delta — bitwise-identical results,
    but the scan computes S-1 prefixes it throws away and XLA does not
    dead-code them, so the tree stays the engine default on CPU hosts."""
    b = get_backend(backend)
    rng = np.random.default_rng(5)
    out: Dict[str, object] = {"backend": backend,
                              "delta_rows": delta_rows, "per_windows": {}}
    for S in window_sizes:
        spec = production_rate_windows(n_windows=S)
        facts = synthetic_facts(rng, delta_rows, N_UNITS)
        seg, vals = spec.segments(facts), spec.values(facts)
        table = b.fold_segments(seg, vals, S)
        b.prefix_fold(table)                         # jit warm-up
        b.fold_segments_scan(seg, vals, S)
        read_scan, read_tree, rratios = [], [], []
        write_tree, write_scan, wratios = [], [], []
        bitwise = True
        for _ in range(reps):
            t0 = time.perf_counter()
            cum = b.prefix_fold(table)
            t1 = time.perf_counter()
            ref = prefix_fold_reference(table)
            t2 = time.perf_counter()
            tree = b.fold_segments(seg, vals, S)
            t3 = time.perf_counter()
            scan = b.fold_segments_scan(seg, vals, S)
            t4 = time.perf_counter()
            bitwise &= (cum.tobytes() == ref.tobytes()
                        and tree.tobytes() == scan.tobytes())
            rs_ms, rt_ms = (t1 - t0) * 1e3, (t2 - t1) * 1e3
            wt_ms, ws_ms = (t3 - t2) * 1e3, (t4 - t3) * 1e3
            read_scan.append(round(rs_ms, 4))
            read_tree.append(round(rt_ms, 4))
            rratios.append(rt_ms / max(rs_ms, 1e-9))
            write_tree.append(round(wt_ms, 4))
            write_scan.append(round(ws_ms, 4))
            wratios.append(wt_ms / max(ws_ms, 1e-9))
        out["per_windows"][str(S)] = {
            "windows": S,
            "read_scan_ms": _median(read_scan),
            "read_per_window_tree_ms": _median(read_tree),
            "read_speedup_scan_vs_per_window_tree":
                round(_median(rratios), 2),
            "write_tree_ms": _median(write_tree),
            "write_scan_ms": _median(write_scan),
            "write_tree_over_scan": round(_median(wratios), 3),
            "bitwise_ok": bitwise,
        }
    largest = out["per_windows"][str(max(window_sizes))]
    out["read_speedup_at_largest"] = \
        largest["read_speedup_scan_vs_per_window_tree"]
    out["bitwise_ok"] = all(v["bitwise_ok"]
                            for v in out["per_windows"].values())
    return out


def summary(quick: bool = False) -> Dict[str, float]:
    """Headline numbers for benchmarks/run.py's CSV report."""
    sizes = (4_000, 16_000) if quick else (10_000, 40_000)
    q = bench_query_latency(sizes, reps=3)
    wl = Workload(n_base=800, waves=2, chunk=800, n_partitions=8,
                  join_depth=2)
    s = bench_staleness(wl)
    bt = bench_batched(8_000 if quick else 40_000, (1024,), reps=3)
    sf = bench_scan_fold((128,), reps=2)
    return {
        "speedup_view_vs_rescan_at_largest": q["speedup_at_largest"],
        "parity_ok": q["parity_ok"],
        "staleness_p95_ms": s["staleness"]["p95_ms"],
        "freshness_p95_ms": s["freshness"]["p95_ms"],
        "staleness_over_freshness_p95":
            s["staleness_p95_over_freshness_p95"],
        "complete": s["complete"],
        "batched_speedup_at_1024": bt["speedup_at_largest"],
        "batched_qps_at_1024": bt["qps_at_largest"],
        "batched_parity_ok": bt["parity_ok"],
        "scan_read_speedup_at_128": sf["read_speedup_at_largest"],
        "scan_bitwise_ok": sf["bitwise_ok"],
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny sizes (CI harness check)")
    ap.add_argument("--backend", default="jax")
    ap.add_argument("--reps", type=int, default=None)
    ap.add_argument("--rate", type=float, default=6_000.0,
                    help="staleness-run arrival rate, records/s "
                         "(0 = firehose; full mode only)")
    ap.add_argument("--out", default="BENCH_views.json")
    args = ap.parse_known_args()[0]

    if args.smoke:
        sizes = (5_000, 20_000)
        reps = args.reps or 3
        threads = (1, 4)
        queries = 200
        conc_rows = 20_000
        batch_sizes = (256, 1024)       # gate needs >= 1024
        scan_windows = (32, 128)        # gate needs >= 128
        wl = Workload(n_base=800, waves=2, chunk=800, n_partitions=8,
                      join_depth=2, backend=args.backend)
    else:
        sizes = (50_000, 200_000, 800_000)
        reps = args.reps or 7
        threads = (1, 4, 16)
        queries = 500
        conc_rows = 200_000
        batch_sizes = (64, 256, 1024, 4096)
        scan_windows = (128, 256, 512)
        # staleness is a STEADY-STATE metric: pace arrival below the
        # host's saturation capacity (firehose arrival measures backlog
        # drain, where the fold stage is starved along with everything
        # else and staleness just mirrors queue depth — see
        # docs/BENCHMARKS.md)
        wl = Workload(n_base=4_000, waves=30, chunk=4_000, join_depth=8,
                      rate=args.rate, backend=args.backend)

    results = {
        "note": ("read-side serving claims; paired/interleaved medians on "
                 "a noisy shared host (docs/BENCHMARKS.md methodology)"),
        "n_units": N_UNITS,
        "query_latency": bench_query_latency(sizes, reps, args.backend),
    }
    print("query_latency:", json.dumps(results["query_latency"]["per_size"],
                                       indent=2))
    results["concurrency"] = bench_concurrency(conc_rows, threads, queries,
                                               args.backend)
    print("concurrency:", json.dumps(results["concurrency"], indent=2))
    results["staleness_e2e"] = bench_staleness(wl)
    print("staleness_e2e:", json.dumps(results["staleness_e2e"], indent=2))
    results["batched"] = bench_batched(conc_rows, batch_sizes, reps,
                                       args.backend)
    print("batched:", json.dumps(results["batched"]["per_batch"], indent=2))
    results["scan_fold"] = bench_scan_fold(scan_windows, max(reps - 2, 2),
                                           args.backend)
    print("scan_fold:", json.dumps(results["scan_fold"]["per_windows"],
                                   indent=2))

    with open(args.out, "w") as f:
        json.dump(results, f, indent=2)
    print(f"wrote {args.out}")


if __name__ == "__main__":
    from repro.core.device import enable_compile_cache
    enable_compile_cache()
    main()
