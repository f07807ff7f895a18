"""Observability overhead benchmark → ``BENCH_observability.json``.

Measures the two costs the observability plane's design pins:

  * ``null_span``  — the DISABLED seam. Every stage hot path runs
    ``with self.tracer.span(...)`` unconditionally; with ``NULL_TRACER``
    that is two attribute lookups, a call returning the shared
    ``_NullSpan`` singleton, and a no-op ``__exit__``. Measured as a
    paired microbenchmark: a representative per-dispatch numeric payload
    bare vs wrapped in a null span, plus the raw ns/span of the seam
    alone. The fraction must stay ≤ 1%.

  * ``paired``     — the ENABLED plane. Alternating full pipeline runs
    (same pre-generated workload, fresh pipeline each cycle) with the
    default ``NULL_TRACER`` vs a live ``StageTracer`` + a registry
    snapshot read at the end. Reported as paired per-cycle throughput
    ratios (traced/null) — on a noisy shared host only the paired ratio
    is meaningful — whose median must stay above 0.95 (≤ 5% overhead).

The traced run's export is also validated (all three sequential stage
seams present, Chrome-trace JSON round-trips) — the ``trace_valid`` gate.

    PYTHONPATH=src python -m benchmarks.observability_overhead [--smoke]

Gated in CI via ``benchmarks/compare_baseline.py`` against
``baselines/BENCH_observability_smoke.json``.
"""
from __future__ import annotations

import argparse
import json
import time
from typing import Dict, List

import numpy as np

from repro.configs.dod_etl import steelworks_config
from repro.core import DODETLPipeline, SourceDatabase
from repro.data.sampler import SamplerConfig, SteelworksSampler
from repro.observability import NULL_TRACER, StageTracer

N_PARTITIONS = 8
N_WORKERS = 2


# ------------------------------------------------------------- null seam
def bench_null_span(payload_rows: int = 4096, iters: int = 200,
                    reps: int = 5) -> Dict[str, float]:
    """Paired medians: representative per-dispatch numeric work bare vs
    wrapped in a NULL_TRACER span, plus the seam's raw ns/span."""
    rng = np.random.default_rng(0)
    a = rng.normal(size=(payload_rows, 16)).astype(np.float32)
    tracer = NULL_TRACER

    def work():
        return float((a * a).sum())

    bare_s, wrapped_s = [], []
    for _ in range(reps):
        t0 = time.perf_counter()
        for _ in range(iters):
            work()
        bare_s.append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        for _ in range(iters):
            with tracer.span("transform.dispatch") as sp:
                work()
                sp.put("records", payload_rows)
        wrapped_s.append(time.perf_counter() - t0)
    bare = float(np.median(bare_s))
    wrapped = float(np.median(wrapped_s))

    # seam alone: span enter/exit with no payload
    n_raw = 200_000
    t0 = time.perf_counter()
    for _ in range(n_raw):
        with tracer.span("x"):
            pass
    raw = time.perf_counter() - t0

    frac = max(0.0, (wrapped - bare) / bare) if bare > 0 else 0.0
    return {
        "payload_rows": payload_rows,
        "bare_us_per_dispatch": round(bare / iters * 1e6, 3),
        "wrapped_us_per_dispatch": round(wrapped / iters * 1e6, 3),
        "ns_per_null_span": round(raw / n_raw * 1e9, 1),
        "null_overhead_fraction": round(frac, 5),
    }


# --------------------------------------------------------- enabled plane
def _build_pipeline(n_records: int, tracer) -> DODETLPipeline:
    import dataclasses
    cfg = steelworks_config(n_partitions=N_PARTITIONS, backend="numpy")
    cfg = dataclasses.replace(cfg, buffer_capacity=4 * n_records)
    src = SourceDatabase()
    SteelworksSampler(cfg, SamplerConfig(
        records_per_table=n_records, n_equipment=N_PARTITIONS,
        late_master_frac=0.02)).generate(src)
    pipe = DODETLPipeline(cfg, src, n_workers=N_WORKERS, tracer=tracer)
    pipe.extract()
    pipe.bootstrap_caches()
    return pipe


def _run_once(n_records: int, traced: bool) -> Dict[str, float]:
    tracer = StageTracer() if traced else NULL_TRACER
    pipe = _build_pipeline(n_records, tracer)
    t0 = time.perf_counter()
    done = pipe.run_to_completion()
    wall = time.perf_counter() - t0
    out = {"records": done, "wall_s": round(wall, 4),
           "records_s": round(done / wall, 1) if wall > 0 else 0.0}
    if traced:
        # read the full plane the way a live poller would
        snap = pipe.metrics.snapshot()
        doc = tracer.to_chrome()
        names = set(tracer.span_names())
        out["span_events"] = len(tracer.events())
        out["trace_valid"] = int(
            {"ingest.fetch", "transform.dispatch", "load.commit"} <= names
            and all(e.get("dur", 0.0) >= 0.0 for e in doc["traceEvents"]
                    if e["ph"] == "X")
            and json.loads(json.dumps(doc)) == doc
            and snap["counters"].get("worker.cache_hits", 0) >= 0)
    return out


def bench_paired(n_records: int, cycles: int) -> Dict[str, object]:
    """Alternating null/traced full-pipeline cycles; the paired per-cycle
    ratio is the noise-robust overhead figure."""
    per_cycle: List[Dict[str, float]] = []
    ratios: List[float] = []
    trace_valid = 1
    for c in range(cycles):
        null = _run_once(n_records, traced=False)
        traced = _run_once(n_records, traced=True)
        trace_valid &= traced.get("trace_valid", 0)
        r = traced["records_s"] / null["records_s"] \
            if null["records_s"] else 0.0
        ratios.append(r)
        per_cycle.append({"cycle": c, "null_records_s": null["records_s"],
                          "traced_records_s": traced["records_s"],
                          "ratio_traced_vs_null": round(r, 4),
                          "span_events": traced.get("span_events", 0)})
    med = float(np.median(ratios))
    return {
        "per_cycle": per_cycle,
        "median_ratio_traced_vs_null": round(med, 4),
        "overhead_enabled_fraction": round(max(0.0, 1.0 - med), 4),
        "trace_valid": int(trace_valid),
    }


def summary(quick: bool = False) -> Dict[str, float]:
    """Small figures for ``benchmarks.run``."""
    n = 2_000 if quick else 6_000
    null = bench_null_span(iters=50 if quick else 200, reps=3)
    paired = bench_paired(n, cycles=1 if quick else 3)
    return {
        "ns_per_null_span": null["ns_per_null_span"],
        "null_overhead_fraction": null["null_overhead_fraction"],
        "median_ratio_traced_vs_null":
            paired["median_ratio_traced_vs_null"],
        "overhead_enabled_fraction": paired["overhead_enabled_fraction"],
        "trace_valid": paired["trace_valid"],
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized: small workload, fewer cycles")
    ap.add_argument("--out", default="BENCH_observability.json")
    args = ap.parse_known_args()[0]

    if args.smoke:
        n, cycles, iters, reps = 2_000, 3, 50, 3
    elif args.quick:
        n, cycles, iters, reps = 4_000, 3, 100, 3
    else:
        n, cycles, iters, reps = 12_000, 5, 200, 5

    results: Dict[str, object] = {
        "workload": {
            "n_records": n, "cycles": cycles,
            "n_partitions": N_PARTITIONS, "n_workers": N_WORKERS,
            "note": ("paired alternating cycles on the sequential "
                     "runtime (deterministic, jit-free numpy backend); "
                     "on a noisy shared container only the paired "
                     "ratios are meaningful (docs/BENCHMARKS.md)"),
        },
    }
    print("null seam: bare vs NULL_TRACER-wrapped dispatch payload")
    results["null_span"] = bench_null_span(iters=iters, reps=reps)
    print(f"  {results['null_span']}")
    print(f"paired: {cycles} null/traced pipeline cycles @ {n} records")
    results["paired"] = bench_paired(n, cycles)
    print(f"  median ratio traced/null: "
          f"{results['paired']['median_ratio_traced_vs_null']}")

    null_frac = results["null_span"]["null_overhead_fraction"]
    enabled_frac = results["paired"]["overhead_enabled_fraction"]
    results["gates"] = {
        "complete": 1,
        "trace_valid": results["paired"]["trace_valid"],
        "null_overhead_ok": int(null_frac <= 0.01),
        "overhead_enabled_ok": int(enabled_frac <= 0.05),
        "throughput_ratio_traced_vs_null":
            results["paired"]["median_ratio_traced_vs_null"],
    }
    print(f"gates: {results['gates']}")
    with open(args.out, "w") as f:
        json.dump(results, f, indent=2)
    print(f"wrote {args.out}")


if __name__ == "__main__":
    from repro.core.device import enable_compile_cache
    enable_compile_cache()
    main()
