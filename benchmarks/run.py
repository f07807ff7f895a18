"""Benchmark harness: one function per paper table/figure plus kernel and
roofline reports. Prints ``name,value,derived`` CSV lines.

    PYTHONPATH=src python -m benchmarks.run [--quick]
"""
from __future__ import annotations

import argparse
import json
import sys


def emit(name: str, payload):
    if isinstance(payload, dict):
        for k, v in payload.items():
            print(f"{name}.{k},{v},")
    elif isinstance(payload, list):
        for row in payload:
            key = row.get("tables", row.get("workers", ""))
            for k, v in row.items():
                if k not in ("tables", "workers"):
                    print(f"{name}[{key}].{k},{v},")
    sys.stdout.flush()


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="reduced record counts (CI-sized)")
    args, _ = ap.parse_known_args()
    n = 4_000 if args.quick else 20_000

    from benchmarks import paper_benchmarks as P

    print("name,value,derived")
    emit("table2_baseline", P.table2_baseline(n))
    emit("fig4_init", P.fig4_init(n_records=n))
    emit("fig5_listener", P.fig5_listener(rows_per_table=max(n // 10, 500)))
    emit("fig6_processor", P.fig6_processor(n_records=n))
    emit("table2_fault", P.table2_fault(n))
    emit("table2_production", P.table2_production(max(n // 4, 1_000)))

    from benchmarks import kernel_bench as K
    emit("kernel.attention", K.bench_attention())
    emit("kernel.gla", K.bench_gla())
    emit("kernel.hash_join", K.bench_hash_join())
    emit("kernel.transform", K.bench_transform())

    # read-side serving layer: incremental-view query speedup, staleness,
    # batched query-plane qps and associative-scan fold speedups (full
    # sweep: python -m benchmarks.report_serving -> BENCH_views.json)
    from benchmarks import report_serving as RS
    emit("serving", RS.summary(quick=args.quick))

    # device-plane dispatch/sync overhead: host<->device round trips per
    # worker step + segment-compacted fold speedup (full sweep:
    # python -m benchmarks.dispatch_overhead -> BENCH_dispatch.json)
    from benchmarks import dispatch_overhead as DO
    emit("dispatch", DO.summary(quick=args.quick))

    # skew-aware adaptive partitioning: worker-load imbalance + surgical
    # cache retention under a Zipf-skewed stream (full sweep:
    # python -m benchmarks.skewed_load -> BENCH_skew.json)
    from benchmarks import skewed_load as SK
    emit("skew", SK.summary(quick=args.quick))

    # crash-consistent durability: recovery cost vs history length +
    # kill -9 exactly-once drill (full sweep:
    # python -m benchmarks.recovery_bench -> BENCH_recovery.json)
    from benchmarks import recovery_bench as RB
    emit("recovery", RB.summary(quick=args.quick))

    # observability plane: NULL_TRACER seam cost + enabled tracer/registry
    # overhead as paired throughput ratios (full sweep:
    # python -m benchmarks.observability_overhead -> BENCH_observability.json)
    from benchmarks import observability_overhead as OO
    emit("observability", OO.summary(quick=args.quick))

    # self-healing control plane: grey-failure detection latency + poison
    # quarantine contracts (full sweep incl. the load-spike drill:
    # python -m benchmarks.control_loop -> BENCH_control.json)
    from benchmarks import control_loop as CL
    emit("control", CL.summary(quick=args.quick))

    # sharded serving plane: modeled fold-throughput scaling across
    # simulated device shards + bitwise parity contracts (full sweep
    # incl. the 4-device mesh drill:
    # python -m benchmarks.shard_scaling -> BENCH_shard.json)
    from benchmarks import shard_scaling as SH
    emit("shard", SH.summary(quick=args.quick))

    # roofline summary (if the dry-run matrix has been produced)
    try:
        from benchmarks.roofline import load_cells, roofline_fraction
        rows = load_cells()
        if rows:
            fracs = [roofline_fraction(r) for r in rows]
            fracs = [f for f in fracs if f]
            emit("roofline", {
                "cells": len(rows),
                "mean_fraction": round(sum(fracs) / len(fracs), 4),
                "min_fraction": round(min(fracs), 4),
                "max_fraction": round(max(fracs), 4),
            })
    except Exception as e:  # pragma: no cover
        print(f"roofline.error,{e},")


if __name__ == "__main__":
    from repro.core.device import enable_compile_cache
    enable_compile_cache()
    main()
