"""Sustained-load cluster benchmark — the paper-claim suite (Table 2 +
Fig. 5).

A closed-loop generator replays the steelworks workload against the source
database at a target arrival rate (default: firehose, i.e. as fast as the
feeder can write — which measures saturation capacity, the plateau of the
paper's Fig. 5). The same workload is then driven through three harnesses:

  * ``sequential``  — the single-worker sequential round loop
                      (``extract(); step()`` — the pre-concurrency
                      architecture), the scaling reference,
  * ``dodetl``      — ``ConcurrentCluster`` sweeping worker counts
                      (1/2/4/8): real threads, real hand-off queues, CDC
                      polled by its own extraction thread; per-run
                      p50/p95/p99 end-to-end freshness is reported from
                      the CDC append event-time stamps,
  * ``baseline``    — the §4.1.1 record-at-a-time processor with
                      per-record source look-backs (time-budgeted: its
                      sustained rate is measured over the budget window,
                      since finishing the full workload record-at-a-time
                      would take minutes).

Deep join chains (``--join-depth``, default 8) replay §4.1.4's normalized
ISA-95 schema cost so the numeric core — not Python dispatch — dominates,
which is also what lets worker threads scale: XLA is pinned to ONE intra-op
thread (set before jax import) so worker-level parallelism is the only
parallelism, exactly one core per worker as in the paper's cluster.

    PYTHONPATH=src python -m benchmarks.sustained_load [--smoke] [--rate R]

Writes ``BENCH_sustained.json`` (see docs/BENCHMARKS.md for the metric
definitions and how the speedups map onto the paper's Table 2).
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import threading
import time
from typing import Dict, Optional

# pin XLA intra-op parallelism BEFORE jax initializes: each worker thread
# owns one core, matching one-core-per-node cluster accounting
_PIN = "--xla_cpu_multi_thread_eigen=false intra_op_parallelism_threads=1"
if "xla_cpu_multi_thread_eigen" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "") + " " + _PIN).strip()

import numpy as np

from repro.configs.dod_etl import ETLConfig, steelworks_config
from repro.core import BaselineStreamProcessor, DODETLPipeline, SourceDatabase
from repro.core.records import RecordBatch
from repro.data.sampler import SamplerConfig, SteelworksSampler
from repro.runtime.cluster import ConcurrentCluster


@dataclasses.dataclass
class Workload:
    n_base: int = 4_000        # base records/table (masters + backlog)
    waves: int = 119           # streamed production-only waves (480k total
                               # operational records: long enough that
                               # thread startup/drain overheads vanish and
                               # shared-host noise averages into every run)
    chunk: int = 4_000         # records per wave (<= n_base: join keys
                               # must exist in the base master tables)
    n_partitions: int = 20     # paper: 20
    join_depth: int = 32       # §4.1.4 normalized-schema join chain
    late_frac: float = 0.05
    rate: float = 0.0          # target arrival rate (records/s); 0=firehose
    backend: str = "jax"
    dispatch: int = 8192       # target records per transform dispatch: the
                               # per-partition fetch cap is derived per
                               # worker count so every configuration issues
                               # same-sized dispatches (uniform jit buckets,
                               # uniform per-dispatch overhead)

    def cap_for(self, n_workers: int) -> int:
        """Per-partition fetch cap giving ~`dispatch` records per coalesced
        fetch when `n_partitions` is spread over `n_workers` workers."""
        owned = max(1, self.n_partitions // max(1, n_workers))
        return max(1, self.dispatch // owned)

    @property
    def total_ops(self) -> int:
        return self.n_base + self.waves * self.chunk


def make_config(wl: Workload) -> ETLConfig:
    cfg = steelworks_config(n_partitions=wl.n_partitions, backend=wl.backend)
    # pre-size caches (no mid-run grow/recompile) and the late buffer (the
    # replicated store must absorb the whole cold-start backlog)
    slots = 1 << max(12, (4 * wl.n_base).bit_length())
    return dataclasses.replace(cfg, cache_slots=slots,
                               buffer_capacity=2 * wl.total_ops)


def seed_source(wl: Workload):
    cfg = make_config(wl)
    src = SourceDatabase()
    sampler = SteelworksSampler(cfg, SamplerConfig(
        records_per_table=wl.n_base, n_equipment=wl.n_partitions,
        late_master_frac=wl.late_frac))
    sampler.generate(src)               # masters + base backlog + late tail
    return cfg, src, sampler


def feed_waves(sampler: SteelworksSampler, src: SourceDatabase,
               wl: Workload) -> None:
    """Closed-loop feeder: apply `waves` production-only chunks, pacing to
    the target arrival rate (sleeping off any time the apply did not use)."""
    interval = (wl.chunk / wl.rate) if wl.rate > 0 else 0.0
    next_t = time.perf_counter()
    for _ in range(wl.waves):
        sampler.generate(src, n_per_table=wl.chunk, tables=("production",))
        if interval:
            next_t += interval
            pause = next_t - time.perf_counter()
            if pause > 0:
                time.sleep(pause)


def prewarm(pipe: DODETLPipeline, wl: Workload) -> None:
    """Compile every transform bucket a run can hit, outside the window —
    BOTH kernel variants: the plain transform (legacy/pre-PR arms) and the
    fused transform+rollup the device-resident hot path dispatches. The
    micro-batch cap bounds any single dispatch (fetch OR retry sweep) to
    cap * n_partitions records, so the bucket set is small and identical
    for every worker count — no mid-measurement jit compiles."""
    be = pipe.backend
    if not be.device:
        return
    w = pipe.workers[0]
    n_units = pipe.cfg.n_business_keys
    size = 256 if be.name == "pallas" else 128
    top = 1 << (2 * wl.dispatch - 1).bit_length()
    while size <= top:
        dummy = np.full((size, 8), -1.0, np.float32)
        be.transform(dummy, w.equipment, w.quality, join_depth=wl.join_depth)
        be.transform_and_rollup(dummy, w.equipment, w.quality,
                                n_units=n_units,
                                join_depth=wl.join_depth).to_host()
        size *= 2


# ----------------------------------------------------------------- harnesses
def _drive_sequential(wl: Workload, step, fused_rollup: bool = True) -> Dict:
    cfg, src, sampler = seed_source(wl)
    pipe = DODETLPipeline(cfg, src, n_workers=1, join_depth=wl.join_depth)
    if not fused_rollup:
        # faithful seed dispatch: no fused rollup riding the transform
        # kernel (the seed arm must not pay post-PR per-dispatch work)
        for w in pipe.workers:
            w.transformer.n_units = None
    prewarm(pipe, wl)
    feeder = threading.Thread(target=feed_waves, args=(sampler, src, wl))
    total, stalls = 0, 0
    t0 = time.perf_counter()
    feeder.start()
    while total < wl.total_ops and stalls < 200:
        pipe.extract()
        n = step(pipe)
        total += n
        stalls = stalls + 1 if n == 0 else 0
    wall = time.perf_counter() - t0
    feeder.join()
    return {"records": total, "wall_s": round(wall, 4),
            "records_s": round(total / wall) if wall else 0}


def run_seed_sequential(wl: Workload) -> Dict:
    """THE scaling reference of the issue: the seed's single-worker
    sequential round loop — workers executed one after another, one
    dispatch PER PARTITION per topic per round (the execution model
    ``SimulatedCluster`` drove before the concurrent runtime existed;
    same reproduction as ``backend_bench``'s legacy arm)."""
    cap = wl.cap_for(1)

    def step(pipe):
        done = 0
        for w in pipe.workers:
            w.pump_master(pipe.master_topic_map["equipment"], w.equipment)
            w.pump_master(pipe.master_topic_map["quality"], w.quality)
        for w in pipe.workers:
            for topic in pipe.operational_topics:
                for p in w.partitions:
                    batch = pipe.queue.consume(w.group, topic, p, cap)
                    if len(batch):
                        pipe.queue.commit(w.group, topic, p, len(batch))
                    facts, _ = w.transformer.process(batch)
                    w.warehouse.load(p, facts)
                    done += len(facts)
        return done

    return _drive_sequential(wl, step, fused_rollup=False)


def run_sequential(wl: Workload) -> Dict:
    """This repo's OPTIMIZED single-thread pipeline (coalesced
    ``extract(); step()`` round loop) — a strictly stronger reference than
    the seed round loop, reported alongside it for transparency."""
    cap = wl.cap_for(1)
    return _drive_sequential(wl, lambda pipe: pipe.step(cap))


def run_concurrent(wl: Workload, n_workers: int) -> Dict:
    cfg, src, sampler = seed_source(wl)
    pipe = DODETLPipeline(cfg, src, n_workers=n_workers,
                          join_depth=wl.join_depth)
    prewarm(pipe, wl)
    cluster = ConcurrentCluster(
        pipe, max_records_per_partition=wl.cap_for(n_workers))
    feeder = threading.Thread(target=feed_waves, args=(sampler, src, wl))
    t0 = time.perf_counter()
    cluster.start()
    feeder.start()
    feeder.join()
    done = cluster.run_until_idle(timeout=600.0)
    wall = time.perf_counter() - t0
    lat = cluster.freshness()
    cluster.stop_all()
    out = {"records": done, "wall_s": round(wall, 4),
           "records_s": round(done / wall) if wall else 0,
           "complete": done == wl.total_ops}
    out.update(lat)
    return out


def run_baseline(wl: Workload, budget_s: float) -> Dict:
    """§4.1.1 record-at-a-time with per-record source look-backs, measured
    over a time budget (its sustained rate is constant once the master
    tables are fully populated, so the window is representative)."""
    cfg, src, sampler = seed_source(wl)
    for _ in range(wl.waves):           # full workload, applied up front
        sampler.generate(src, n_per_table=wl.chunk, tables=("production",))
    baseline = BaselineStreamProcessor(cfg, src)
    prod_tid = [t.name for t in cfg.tables].index("production")
    # extract through the public log read path (what a Listener does)
    log_all, _ = src.log.read_from(0)
    prod = log_all.filter(log_all.table_id == prod_tid)
    done = 0
    t0 = time.perf_counter()
    # micro-batches of 256 records, like a stream framework's trigger
    for lo in range(0, len(prod), 256):
        sub = prod.take(np.arange(lo, min(lo + 256, len(prod))))
        done += len(baseline.process(sub))
        if time.perf_counter() - t0 > budget_s:
            break
    wall = time.perf_counter() - t0
    return {"records": done, "wall_s": round(wall, 4),
            "records_s": round(done / wall) if wall else 0,
            "budget_s": budget_s, "total_available": wl.total_ops,
            "lookups": src.lookup_count}


def median(runs, key="records_s"):
    runs = sorted(runs, key=lambda r: r[key])
    return runs[len(runs) // 2]


def main() -> None:
    import sys
    # with ~3 threads per worker on a small host, the default 5 ms GIL
    # switch interval forces frequent handoffs mid-hot-loop; a longer
    # quantum lets each stage finish its numpy/XLA call (which releases
    # the GIL anyway) before yielding
    sys.setswitchinterval(0.02)
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny workload, 2 workers (CI harness check)")
    ap.add_argument("--rate", type=float, default=0.0,
                    help="target arrival rate in records/s (0 = firehose)")
    ap.add_argument("--join-depth", type=int, default=None)
    ap.add_argument("--dispatch", type=int, default=8192,
                    help="target records per transform dispatch")
    ap.add_argument("--backend", default="jax")
    ap.add_argument("--repeats", type=int, default=None)
    ap.add_argument("--baseline-budget-s", type=float, default=None)
    ap.add_argument("--out", default="BENCH_sustained.json")
    args = ap.parse_known_args()[0]

    if args.smoke:
        wl = Workload(n_base=800, waves=2, chunk=800, n_partitions=8,
                      join_depth=args.join_depth or 2, rate=args.rate,
                      backend=args.backend, dispatch=args.dispatch)
        worker_counts = (2,)
        repeats = args.repeats or 1
        budget = args.baseline_budget_s or 3.0
    else:
        wl = Workload(rate=args.rate, backend=args.backend,
                      join_depth=args.join_depth or 32,
                      dispatch=args.dispatch)
        worker_counts = (1, 2, 4, 8)
        repeats = args.repeats or 5
        budget = args.baseline_budget_s or 20.0

    results: Dict[str, dict] = {
        "workload": {
            **dataclasses.asdict(wl), "total_ops": wl.total_ops,
            "host_cores": os.cpu_count(),
            "note": ("firehose arrival (rate=0) measures saturation "
                     "capacity, the Fig. 5 plateau; XLA pinned to one "
                     "intra-op thread so worker threads are the only "
                     "parallelism"),
        },
        "sequential": {}, "dodetl": {},
    }

    # PAIRED cycles (seed-loop, coalesced-loop, w1, w2, ... adjacent in
    # time, repeated): the shared host's speed drifts at the seconds
    # timescale, so each cycle's concurrent runs are ratioed against the
    # sequential runs next to them in time, and the headline ratio is the
    # median over cycles — a paired estimator that cancels drift a plain
    # median of rates cannot
    seed_runs, seq_runs = [], []
    conc_runs: Dict[int, list] = {n: [] for n in worker_counts}
    paired_seed: Dict[int, list] = {n: [] for n in worker_counts}
    paired_coal: Dict[int, list] = {n: [] for n in worker_counts}
    for _ in range(repeats):
        sd = run_seed_sequential(wl)
        seed_runs.append(sd)
        s = run_sequential(wl)
        seq_runs.append(s)
        for n in worker_counts:
            c = run_concurrent(wl, n)
            conc_runs[n].append(c)
            paired_seed[n].append(c["records_s"] / max(sd["records_s"], 1))
            paired_coal[n].append(c["records_s"] / max(s["records_s"], 1))
    seed = median(seed_runs)
    seed["records_s_runs"] = [r["records_s"] for r in seed_runs]
    seed["note"] = ("the issue's reference: seed-era round loop, one "
                    "dispatch per partition per topic per round")
    results["sequential"]["1"] = seed
    seq = median(seq_runs)
    seq["records_s_runs"] = [r["records_s"] for r in seq_runs]
    seq["note"] = "this PR's optimized coalesced single-thread round loop"
    results["sequential"]["1_coalesced"] = seq
    print(f"sequential/1 (seed round loop): {seed}")
    print(f"sequential/1_coalesced: {seq}")
    for n in worker_counts:
        res = median(conc_runs[n])
        res["records_s_runs"] = [r["records_s"] for r in conc_runs[n]]
        results["dodetl"][str(n)] = res
        print(f"dodetl/{n}: {res}")

    base = run_baseline(wl, budget)
    results["baseline"] = base
    print(f"baseline: {base}")

    results["speedup_vs_baseline"] = {
        n: round(r["records_s"] / max(base["records_s"], 1), 2)
        for n, r in results["dodetl"].items()}

    def ratio_block(paired: Dict[int, list]) -> Dict:
        out = {
            str(n): {"median_paired_ratio":
                     round(sorted(rs)[len(rs) // 2], 2),
                     "paired_ratios": [round(r, 2) for r in rs]}
            for n, rs in paired.items()}
        multi = [v["median_paired_ratio"] for n, v in out.items()
                 if int(n) > 1]
        out["best_multi_worker"] = max(multi) if multi else None
        return out

    results["concurrent_vs_sequential"] = ratio_block(paired_seed)
    results["concurrent_vs_sequential"]["reference"] = \
        "sequential.1 (the seed-era single-worker round loop)"
    results["concurrent_vs_coalesced_sequential"] = ratio_block(paired_coal)
    results["concurrent_vs_coalesced_sequential"]["reference"] = \
        "sequential.1_coalesced (this PR's optimized single-thread loop)"
    print(f"speedup vs baseline: {results['speedup_vs_baseline']}")
    print(f"concurrent vs seed round loop: "
          f"{results['concurrent_vs_sequential']}")
    print(f"concurrent vs coalesced sequential: "
          f"{results['concurrent_vs_coalesced_sequential']}")

    with open(args.out, "w") as f:
        json.dump(results, f, indent=2)
    print(f"wrote {args.out}")


if __name__ == "__main__":
    from repro.core.device import enable_compile_cache
    enable_compile_cache()
    main()
