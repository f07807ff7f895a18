"""Skew-aware adaptive partitioning benchmark → ``BENCH_skew.json``.

The paper's load-balancing argument (§3.1.1 "efficient data partitioning")
under a REALISTIC key distribution: a steelworks emits most production
events from a few hot units (casters), so business keys are drawn
Zipf(s)-skewed for s ∈ {0, 0.8, 1.2}. Under static ``hash % n`` routing a
hot key pins one partition — and its worker — while the rest idle; the
skew-aware strategy observes the broker's per-key publish load mid-run and
splits hot hash ranges / merges cold ones (``ConcurrentCluster.
repartition`` / ``DODETLPipeline.repartition``), with surgical cache
migration keeping the survivors warm.

Two harnesses per (s, strategy):

* **modeled** — the deterministic barrier loop (the ``SimulatedCluster``
  execution model: per-round cluster time = max over workers). Per-worker
  record counts are exactly reproducible, so the worker-load **imbalance
  ratio** (max/mean records per worker) and the **cache-retention
  fraction** of the mid-run repartition are noise-free — these are the CI
  gates. Modeled throughput ratios (skew vs static per interleaved cycle,
  median over cycles) show what balance buys a cluster with one core per
  worker.
* **concurrent** — the real ``ConcurrentCluster`` (4 workers × 3 stage
  threads) on the same workload, paired static/skew cycles adjacent in
  time. On the noisy shared 2-core container that produced the checked-in
  file, total work — not per-worker balance — bounds wall time, so this
  arm under-reports the balance dividend; trust only the paired medians
  and read docs/BENCHMARKS.md before comparing absolute rates.

Every arm asserts zero record loss and that static and skew runs produce
byte-identical canonical warehouses (routing must never change WHAT is
computed).

    PYTHONPATH=src python -m benchmarks.skewed_load [--quick|--smoke]
"""
from __future__ import annotations

import argparse
import json
import time
from typing import Dict, Optional

import numpy as np

from repro.configs.dod_etl import steelworks_config
from repro.core import DODETLPipeline, SourceDatabase
from repro.data.sampler import SamplerConfig, SteelworksSampler
from repro.runtime.cluster import ConcurrentCluster

ZIPFS = (0.0, 0.8, 1.2)


def build(strategy: str, zipf_s: float, n_base: int, n_partitions: int,
          n_workers: int, n_units: int, seed: int = 0):
    """Seed masters + a base operational backlog; production WAVES are
    then streamed by the caller (the repartition must have a future to
    redirect — routing epochs only steer records published after the
    switch; the already-published backlog drains under its old epoch).

    ``n_units`` is deliberately larger than the paper's 20 (a finer
    business-key grain — think production lines, not areas): a business
    key is the ATOMIC unit of worker affinity, so under Zipf(1.2) over
    only 20 keys the single hottest key carries ~35% of the stream and
    NO strategy can balance 4 workers below max/mean ≈ 1.4. At 200 keys
    the hot key is ~20% < the 25% per-worker mean, so balance is
    achievable — and the strategies can be told apart."""
    cfg = steelworks_config(n_partitions=n_partitions, backend="numpy",
                            partition_strategy=strategy)
    import dataclasses as _dc
    cfg = _dc.replace(cfg, buffer_capacity=65536, n_business_keys=n_units)
    src = SourceDatabase()
    sampler = SteelworksSampler(cfg, SamplerConfig(
        records_per_table=n_base, n_equipment=n_units,
        zipf_s=zipf_s, seed=seed))
    sampler.generate(src)
    pipe = DODETLPipeline(cfg, src, n_workers=n_workers)
    return pipe, sampler, src


def _imbalance(counts: Dict[str, int]) -> float:
    v = np.array(list(counts.values()), float)
    return float(v.max() / v.mean()) if v.sum() else 1.0


def run_modeled(strategy: str, zipf_s: float, n_base: int, waves: int,
                chunk: int, n_partitions: int, n_workers: int, cap: int,
                repartition_round: int, adapt: bool,
                n_units: int = 200) -> Dict:
    """Deterministic barrier rounds, fed one production wave per round so
    publishes are spread across routing epochs. ``adapt`` fires
    ``pipe.repartition()`` after ``repartition_round`` rounds — mid-run,
    with the broker's load counters warmed, exactly like a coordinator
    watching its metrics.

    The primary cluster-time figure is the UNIT-COST barrier model:
    per-round cost = max over workers of records processed that round
    (one core per worker, uniform per-record cost), summed over rounds.
    It is exactly reproducible — per-worker record counts are
    deterministic — which is what the noisy shared host demands (see
    docs/BENCHMARKS.md); the measured max-wall sum is reported next to it
    for transparency but inherits the host's scheduler noise."""
    total = n_base + waves * chunk
    pipe, sampler, src = build(strategy, zipf_s, n_base, n_partitions,
                               n_workers, n_units)
    pipe.extract()
    pipe.bootstrap_caches()
    eq = pipe.master_topic_map["equipment"]
    qu = pipe.master_topic_map["quality"]
    walls, round_costs = [], []
    done, rounds, stalls, fed = 0, 0, 0, 0
    migration = None
    pre_counts: Optional[Dict[str, int]] = None
    while True:
        if fed < waves:
            sampler.generate(src, n_per_table=chunk, tables=("production",))
            fed += 1
        pipe.extract()
        for w in pipe.workers:
            w.pump_master(eq, w.equipment)
            w.pump_master(qu, w.quality)
        round_walls, worker_records, got = [], [], 0
        for w in pipe.workers:
            t0 = time.perf_counter()
            got_w = 0
            for topic in pipe.operational_topics:
                got_w += w.process_operational(topic, cap)
            round_walls.append(time.perf_counter() - t0)
            worker_records.append(got_w)
            got += got_w
        walls.append(max(round_walls))
        round_costs.append((max(worker_records), got))
        done += got
        rounds += 1
        if adapt and rounds == repartition_round:
            pre_counts = {w.name: w.metrics.records for w in pipe.workers}
            migration = pipe.repartition()
        buffered = sum(len(w.buffer) for w in pipe.workers)
        stalls = stalls + 1 if got == 0 else 0
        if fed >= waves and ((got == 0 and buffered == 0) or stalls >= 3):
            break
    counts = {w.name: w.metrics.records for w in pipe.workers}
    unit_cost = sum(c for c, _ in round_costs)
    # sustained window: rounds after the adaptation point (the SAME index
    # split in the static arm, so both arms are compared on the part of
    # the stream a steady-state cluster would spend its life in)
    sus_cost = sum(c for c, _ in round_costs[repartition_round:])
    sus_records = sum(g for _, g in round_costs[repartition_round:])
    out = {
        "records": done,
        "rounds": rounds,
        "cluster_cost_records": unit_cost,   # Σ max worker records/round
        "throughput_modeled": round(done / unit_cost, 4) if unit_cost else 0,
        "records_sustained": sus_records,
        "throughput_sustained": round(sus_records / sus_cost, 4)
        if sus_cost else 0,
        "measured_wall_s": round(sum(walls), 4),
        "imbalance": round(_imbalance(counts), 4),
        "per_worker_records": counts,
        "complete": done == total,
    }
    if migration is not None:
        post = {w: counts[w] - pre_counts.get(w, 0) for w in counts}
        out["imbalance_pre"] = round(_imbalance(pre_counts), 4)
        out["imbalance_post"] = round(_imbalance(post), 4)
        out["migration"] = migration
    return out, pipe


def run_concurrent(strategy: str, zipf_s: float, n_base: int, waves: int,
                   chunk: int, n_partitions: int, n_workers: int, cap: int,
                   adapt: bool, repartition_frac: float = 0.25,
                   n_units: int = 200) -> Dict:
    """The real cluster on the same workload: CDC extraction thread +
    3 stage threads per worker, a feeder thread streaming production
    waves; the skew arm repartitions once ~25% of the stream has landed
    (load metrics warmed, most of the stream still ahead)."""
    import threading
    total = n_base + waves * chunk
    pipe, sampler, src = build(strategy, zipf_s, n_base, n_partitions,
                               n_workers, n_units)

    def feed():
        for _ in range(waves):
            sampler.generate(src, n_per_table=chunk, tables=("production",))
            time.sleep(0.002)        # let extraction interleave the waves

    cluster = ConcurrentCluster(pipe, max_records_per_partition=cap)
    cluster.start()
    feeder = threading.Thread(target=feed)
    feeder.start()
    migration = None
    if adapt:
        deadline = time.time() + 60
        while cluster.records_done() < total * repartition_frac \
                and time.time() < deadline:
            time.sleep(0.005)
        migration = cluster.repartition()
    feeder.join()
    done = cluster.run_until_idle(timeout=180)
    cluster.stop_all()
    rep = cluster.report()
    counts = {name: rt.records_done
              for name, rt in cluster.runtimes.items() if not rt.dead}
    out = {
        "records": done,
        "records_s": rep["records_s"],
        "wall_s": rep["wall_s"],
        "imbalance": round(_imbalance(counts), 4),
        "complete": done == total,
    }
    if migration is not None:
        out["migration"] = migration
    return out, pipe


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized: s=1.2 only, 1 cycle, small workload")
    ap.add_argument("--out", default="BENCH_skew.json")
    args = ap.parse_known_args()[0]

    if args.smoke:
        n_base, chunk, waves, cycles, zipfs = 500, 500, 4, 1, (1.2,)
    elif args.quick:
        n_base, chunk, waves, cycles, zipfs = 1_000, 1_000, 9, 2, (1.2,)
    else:
        n_base, chunk, waves, cycles, zipfs = 2_000, 2_000, 9, 3, ZIPFS
    n = n_base + waves * chunk
    n_partitions, n_workers, cap = 20, 4, 200
    modeled_cap = None      # the barrier arm is uncapped: a per-partition
                            # fetch cap would throttle the deliberately
                            # load-concentrated hot partitions' drain and
                            # measure the cap, not the balance
    n_units = 10 * n_partitions
    repartition_round = 3

    results = {
        "workload": {
            "n_base": n_base, "chunk": chunk, "waves": waves,
            "total_ops": n, "n_partitions": n_partitions,
            "n_units": n_units, "modeled_cap": modeled_cap,
            "n_workers": n_workers, "max_records_per_partition": cap,
            "zipf_s": list(zipfs), "cycles": cycles,
            "repartition_after_round": repartition_round,
            "note": ("modeled = deterministic barrier rounds (cluster "
                     "time = max worker wall; counts exact); concurrent "
                     "= real ConcurrentCluster — on the noisy 2-core "
                     "container only paired/interleaved medians are "
                     "meaningful (docs/BENCHMARKS.md)"),
        },
        "modeled": {}, "concurrent": {},
    }

    for s in zipfs:
        key = f"zipf_{s}"
        speedups, wall_ratios, stat_runs, skew_runs = [], [], [], []
        table_ref = None
        for _ in range(cycles):          # interleaved: static, skew, ...
            stat, pipe_a = run_modeled("static", s, n_base, waves, chunk,
                                       n_partitions, n_workers, modeled_cap,
                                       repartition_round, adapt=False,
                                       n_units=n_units)
            skew, pipe_b = run_modeled("skew", s, n_base, waves, chunk,
                                       n_partitions, n_workers, modeled_cap,
                                       repartition_round, adapt=True,
                                       n_units=n_units)
            a = pipe_a.warehouse.canonical_fact_table()
            b = pipe_b.warehouse.canonical_fact_table()
            assert a.shape == b.shape and a.tobytes() == b.tobytes(), \
                "routing changed WHAT was computed"
            table_ref = a.shape
            speedups.append(skew["throughput_sustained"]
                            / max(stat["throughput_sustained"], 1e-9))
            wall_ratios.append(stat["measured_wall_s"]
                               / max(skew["measured_wall_s"], 1e-9))
            stat_runs.append(stat)
            skew_runs.append(skew)
        mid = sorted(range(cycles), key=lambda i: speedups[i])[cycles // 2]
        results["modeled"][key] = {
            "static": stat_runs[mid],
            "skew": skew_runs[mid],
            # unit-cost barrier model: deterministic, identical per cycle
            "speedup_sustained_unit_cost": round(speedups[mid], 3),
            "speedup_whole_run_unit_cost": round(
                skew_runs[mid]["throughput_modeled"]
                / max(stat_runs[mid]["throughput_modeled"], 1e-9), 3),
            # measured max-wall ratios: paired per cycle, noisy host
            "paired_measured_wall_ratios": [round(x, 3)
                                            for x in wall_ratios],
            "median_paired_wall_ratio": round(
                sorted(wall_ratios)[cycles // 2], 3),
            "warehouse_byte_identical": True,
            "canonical_shape": list(table_ref),
        }
        print(f"modeled {key}: imbalance static "
              f"{stat_runs[mid]['imbalance']} -> skew "
              f"{skew_runs[mid].get('imbalance_post', skew_runs[mid]['imbalance'])}, "
              f"sustained unit-cost speedup "
              f"{results['modeled'][key]['speedup_sustained_unit_cost']}x "
              f"(whole run "
              f"{results['modeled'][key]['speedup_whole_run_unit_cost']}x, "
              f"measured wall ratio "
              f"{results['modeled'][key]['median_paired_wall_ratio']}x)")

    # real-concurrency probe at the heaviest skew
    s = max(zipfs)
    key = f"zipf_{s}"
    speedups, stat_runs, skew_runs = [], [], []
    for _ in range(cycles):
        stat, _ = run_concurrent("static", s, n_base, waves, chunk,
                                 n_partitions, n_workers, cap, adapt=False,
                                 n_units=n_units)
        skew, _ = run_concurrent("skew", s, n_base, waves, chunk,
                                 n_partitions, n_workers, cap, adapt=True,
                                 n_units=n_units)
        speedups.append(skew["records_s"] / max(stat["records_s"], 1))
        stat_runs.append(stat)
        skew_runs.append(skew)
    mid = sorted(range(cycles), key=lambda i: speedups[i])[cycles // 2]
    results["concurrent"][key] = {
        "static": stat_runs[mid],
        "skew": skew_runs[mid],
        "paired_speedups": [round(x, 3) for x in speedups],
        "median_paired_speedup": round(sorted(speedups)[cycles // 2], 3),
    }
    print(f"concurrent {key}: paired speedup "
          f"{results['concurrent'][key]['median_paired_speedup']}x, "
          f"retention {skew_runs[mid].get('migration', {}).get('cache_retention')}")

    # ------------------------------------------------------------- CI gates
    heavy = results["modeled"][f"zipf_{max(zipfs)}"]
    gates = {
        "complete": all(r["static"]["complete"] and r["skew"]["complete"]
                        for r in results["modeled"].values()),
        "warehouse_byte_identical": all(
            r["warehouse_byte_identical"]
            for r in results["modeled"].values()),
        "cache_retention": heavy["skew"]["migration"]["cache_retention"],
        "imbalance_pre": heavy["skew"]["imbalance_pre"],
        "imbalance_post": heavy["skew"]["imbalance_post"],
        "imbalance_static": heavy["static"]["imbalance"],
    }
    results["gates"] = gates
    print("gates:", gates)

    with open(args.out, "w") as f:
        json.dump(results, f, indent=2)
    print(f"wrote {args.out}")


def summary(quick: bool = False) -> Dict[str, float]:
    """Small single-cycle figures for ``benchmarks.run``."""
    n_base = chunk = 500 if quick else 1_000
    waves = 4
    stat, _ = run_modeled("static", 1.2, n_base, waves, chunk, 20, 4, None,
                          3, adapt=False)
    skew, _ = run_modeled("skew", 1.2, n_base, waves, chunk, 20, 4, None,
                          3, adapt=True)
    return {
        "imbalance_static": stat["imbalance"],
        "imbalance_skew_post": skew.get("imbalance_post", skew["imbalance"]),
        "cache_retention": skew.get("migration", {}).get("cache_retention",
                                                         1.0),
        "modeled_speedup": round(
            skew["throughput_sustained"]
            / max(stat["throughput_sustained"], 1e-9), 3),
        "complete": int(stat["complete"] and skew["complete"]),
    }


if __name__ == "__main__":
    from repro.core.device import enable_compile_cache
    enable_compile_cache()
    main()
