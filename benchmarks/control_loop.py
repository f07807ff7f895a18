"""Self-healing control plane benchmark → ``BENCH_control.json``.

Three questions about the control plane (ARCHITECTURE.md "Control
plane"), each with a CI-gated answer:

* **Spike** — the autonomous elastic loop, end to end: a single-worker
  cluster serves a gently paced CDC stream (pre-spike freshness p95 is
  sampled), then a burst far above the per-worker backlog threshold
  lands at once. The controller must scale the cluster up on its own —
  zero human calls — drain the burst exactly-once, and once the backlog
  is gone the steady-state freshness p95 must return to within 2x the
  pre-spike figure (noise-floored: sub-floor percentiles compare against
  the floor, not against scheduler jitter).
* **Detection** — the grey-failure drill at benchmark scale: one stage
  thread freezes mid-stream; the supervisor must notice the silent
  heartbeat, confirm via in-band ping, force-evict (fencing the zombie's
  consumer group) and restart a re-hydrated replacement. Gated on the
  detection latency (hang instant -> eviction, bounded by the configured
  deadline + grace + supervision ticks) and on the healed stream being
  byte-identical to an uninterrupted sequential oracle.
* **Poison** — a deterministically failing record must be bisected out,
  quarantined in the dead-letter buffer with its offsets COMMITTED, and
  everything else must load — with zero evictions and zero restarts
  (quarantine, not crash-loop).

    PYTHONPATH=src python -m benchmarks.control_loop [--quick|--smoke]
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import time
import warnings
from typing import Dict

import numpy as np

from repro.configs.dod_etl import steelworks_config
from repro.core import DODETLPipeline, SourceDatabase
from repro.data.sampler import SamplerConfig, SteelworksSampler
from repro.durability.faults import TRANSFORM_DONE, FaultInjector
from repro.runtime.cluster import ConcurrentCluster
from repro.runtime.control import ControlConfig, QuiesceTimeoutWarning

N_PARTITIONS = 8
SEED = 11

# the test-suite supervision cadence: sub-second detection without
# flapping on a loaded CI box
FAST = dict(tick_s=0.02, heartbeat_deadline_s=0.4, ping_grace_s=0.2,
            warmup_s=0.2, restart_backoff_s=0.05, restart_backoff_max_s=0.5,
            restart_jitter_s=0.02, policy_interval_s=0.1,
            evict_lock_timeout_s=0.5, evict_join_timeout_s=0.5,
            scaling=False)

# freshness percentiles below this are scheduler noise on the numpy
# backend: the recovery gate compares against max(pre_p95, floor)
FRESHNESS_FLOOR_MS = 25.0


def build(n: int, *, n_workers: int = 1, late_frac: float = 0.0,
          fault=None, seed: int = SEED, tables=None):
    cfg = steelworks_config(n_partitions=N_PARTITIONS, backend="numpy")
    cfg = dataclasses.replace(cfg, buffer_capacity=65536)
    src = SourceDatabase()
    sampler = SteelworksSampler(cfg, SamplerConfig(
        records_per_table=n, n_equipment=N_PARTITIONS,
        late_master_frac=late_frac, seed=seed))
    sampler.generate(src, tables=tables)
    pipe = DODETLPipeline(cfg, src, n_workers=n_workers, fault=fault)
    return cfg, src, pipe, sampler


def _oracle_facts(n: int, late_frac: float = 0.0) -> bytes:
    """Byte-level fact table of an uninterrupted single-worker run."""
    _, _, pipe, _ = build(n, late_frac=late_frac)
    pipe.extract()
    pipe.bootstrap_caches()
    pipe.run_to_completion()
    return pipe.warehouse.canonical_fact_table().tobytes()


def _stop_quietly(cluster) -> None:
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", QuiesceTimeoutWarning)
        cluster.stop_all()


# --------------------------------------------------------------------- spike
# this repo's synthetic numpy transform is deliberately cheap (one worker
# drains a 4k burst in ~10 ms — no backlog ever survives the policy's
# hysteresis window), so the spike arm emulates a production-cost
# transform: a fixed per-record delay on every transform dispatch
SPIKE_COST_PER_RECORD_S = 2e-4          # ~5k records/s per worker


def _slow_transform(worker, per_record_s: float) -> None:
    orig = worker.transformer.transform_block

    def wrapped(batch, eq, qu):
        time.sleep(per_record_s * len(batch))
        return orig(batch, eq, qu)

    worker.transformer.transform_block = wrapped


def _feed(sampler, src, cluster, batches: int, per: int,
          interval_s: float) -> int:
    """Paced CDC feeder: publish `per` production records, let the live
    extract loop tail them, sleep, repeat. Returns records fed."""
    for _ in range(batches):
        sampler.generate(src, n_per_table=per, tables=("production",))
        time.sleep(interval_s)
    cluster.run_until_idle(timeout=120)
    return batches * per


def bench_spike(n0: int, burst: int, *, pace_batches: int = 5,
                pace_per: int = 120, pace_interval_s: float = 0.15,
                max_workers: int = 3) -> Dict:
    """Load-spike drill: paced stream -> burst -> autonomous scale-up ->
    drain -> paced stream again. Gates on the controller acting by
    itself, exactly-once completion, and steady-state freshness p95
    recovering to within 2x the pre-spike figure.

    ``n0`` must be >= ``burst``: the seed generate creates the master
    rows (quality inspections join per prod_id) for prod_ids 0..n0-1,
    and the sampler's follow-up production-only waves reuse prod_ids
    0..k-1 — a wave larger than the seeded key space would late-buffer
    forever. Only MASTER tables are seeded (the production stream
    arrives exclusively through the paced feed), so the burst is the
    one backlog event the controller ever sees."""
    assert n0 >= burst and n0 >= pace_per
    ctl = ControlConfig(**{**FAST, "scaling": True,
                           "policy_interval_s": 0.05,
                           "hysteresis_samples": 2, "cooldown_s": 0.3,
                           "backlog_high_per_worker": 500,
                           "backlog_low_per_worker": 0,
                           "scale_down": False, "repartition": False,
                           "max_workers": max_workers})
    cfg, src, pipe, sampler = build(n0, n_workers=1,
                                    tables=("equipment", "quality"))
    for w in pipe.workers:
        _slow_transform(w, SPIKE_COST_PER_RECORD_S)
    orig_new_worker = pipe._new_worker

    def _new_worker(name, join_depth):        # controller-spawned workers
        w = orig_new_worker(name, join_depth)  # carry the same cost model
        _slow_transform(w, SPIKE_COST_PER_RECORD_S)
        return w

    pipe._new_worker = _new_worker
    cluster = ConcurrentCluster(pipe, max_records_per_partition=100,
                                poll_cdc=True, control=ctl)
    cluster.start()
    total = 0
    cluster.run_until_idle(timeout=120)           # pump the master seed
    # two unmeasured waves warm the cold code paths (first-dispatch cost
    # would inflate the pre-spike p95 and soften the recovery gate)
    total += _feed(sampler, src, cluster, 2, pace_per, pace_interval_s)

    # phase A: gentle paced stream — the pre-spike freshness window
    cluster.freshness(drain=True)                 # discard warmup samples
    total += _feed(sampler, src, cluster, pace_batches, pace_per,
                   pace_interval_s)
    pre = cluster.freshness(drain=True)
    workers_pre = len(cluster.alive_workers())

    # phase B: the burst, all at once
    sampler.generate(src, n_per_table=burst, tables=("production",))
    total += burst
    t0 = time.perf_counter()
    cluster.run_until_idle(timeout=300)
    t_drain = time.perf_counter() - t0
    workers_post = len(cluster.alive_workers())
    cluster.freshness(drain=True)                 # discard the spike window

    # phase C: gentle paced stream again — post-recovery steady state
    total += _feed(sampler, src, cluster, pace_batches, pace_per,
                   pace_interval_s)
    post = cluster.freshness(drain=True)
    snap = cluster.control.snapshot()
    _stop_quietly(cluster)

    pre95 = max(float(pre["p95_ms"]), FRESHNESS_FLOOR_MS)
    post95 = max(float(post["p95_ms"]), FRESHNESS_FLOOR_MS)
    out = {
        "master_key_space": int(n0),
        "burst_records": int(burst),
        "total_records": int(total),
        "rows_loaded": int(pipe.warehouse.rows_loaded),
        "workers_pre_spike": int(workers_pre),
        "workers_post_spike": int(workers_post),
        "scale_ups": int(snap["scale_ups"]),
        "human_calls": 0,                          # autonomous by construction
        "burst_drain_wall_s": round(t_drain, 3),
        "freshness_pre_p95_ms": round(float(pre["p95_ms"]), 3),
        "freshness_post_p95_ms": round(float(post["p95_ms"]), 3),
        "freshness_floor_ms": FRESHNESS_FLOOR_MS,
        "recovery_ratio": round(post95 / pre95, 3),
        "complete": bool(pipe.warehouse.rows_loaded == total),
        "controller_acted": bool(snap["scale_ups"] >= 1
                                 and workers_post > workers_pre),
        "spike_recovered": bool(post95 <= 2.0 * pre95),
        "controller_crashed": bool(snap["crashed"]),
    }
    print(f"  spike: {total} records, burst {burst} drained in "
          f"{t_drain:.2f}s, workers {workers_pre}->{workers_post} "
          f"({snap['scale_ups']} scale-ups, 0 human calls), freshness p95 "
          f"{out['freshness_pre_p95_ms']} -> {out['freshness_post_p95_ms']} "
          f"ms (ratio {out['recovery_ratio']})")
    return out


# ----------------------------------------------------------------- detection
def bench_detection(n: int) -> Dict:
    """Grey-failure drill: hang a transform stage mid-stream, measure the
    supervisor's hang->eviction latency, verify the healed stream is
    byte-identical to the uninterrupted sequential oracle."""
    fault = FaultInjector({TRANSFORM_DONE: 3},
                          actions={TRANSFORM_DONE: "hang"})
    cfg, _, pipe, _ = build(n, n_workers=3, fault=fault)
    pipe.extract()
    cluster = ConcurrentCluster(pipe, poll_cdc=False,
                                max_records_per_partition=25,
                                control=ControlConfig(**FAST))
    cluster.start()
    assert fault.hung.wait(20.0), "hang seam never reached"
    done = cluster.run_until_idle(timeout=120)
    _stop_quietly(cluster)
    fault.release_hangs()

    ev = cluster.control.last_eviction
    latency = (ev["at_s"] - fault.hung_at_s) if ev else float("inf")
    bound = (FAST["heartbeat_deadline_s"] + FAST["ping_grace_s"]
             + 10 * FAST["tick_s"] + 2 * FAST["evict_join_timeout_s"] + 1.5)
    identical = (pipe.warehouse.canonical_fact_table().tobytes()
                 == _oracle_facts(n))
    snap = cluster.control.snapshot()
    out = {
        "records": int(n),
        "heartbeat_deadline_s": FAST["heartbeat_deadline_s"],
        "latency_s": round(latency, 3),
        "latency_bound_s": round(bound, 3),
        "evictions": int(snap["evictions"]),
        "restarts": int(snap["restarts"]),
        "rows_loaded": int(pipe.warehouse.rows_loaded),
        "complete": bool(done == n and pipe.warehouse.rows_loaded == n),
        "detection_within_bound": bool(ev is not None
                                       and 0 < latency < bound),
        "byte_identical": bool(identical),
        "restart_ok": bool(ev is not None and ev["restarted"]),
    }
    print(f"  detection: hang -> eviction in {out['latency_s']}s "
          f"(bound {out['latency_bound_s']}s), restarted="
          f"{out['restart_ok']}, byte_identical={identical}")
    return out


# -------------------------------------------------------------------- poison
class _PoisonError(Exception):
    pass


def _poison_transform(worker, key: int) -> None:
    orig = worker.transformer.transform_block

    def wrapped(batch, eq, qu):
        if np.any(batch.business_key == key):
            raise _PoisonError(f"poison key {key}")
        return orig(batch, eq, qu)

    worker.transformer.transform_block = wrapped


def bench_poison(n: int, bad_key: int = 3) -> Dict:
    """Poison-record drill: quarantine to the dead-letter buffer with
    committed offsets; zero evictions, zero restarts, no crash loop."""
    cfg, _, pipe, _ = build(n, n_workers=2)
    for w in pipe.workers:
        _poison_transform(w, bad_key)
    pipe.extract()
    cluster = ConcurrentCluster(pipe, poll_cdc=False,
                                control=ControlConfig(**FAST))
    cluster.start()
    cluster.run_until_idle(timeout=120)
    cluster.stop_all()

    quarantined = sum(len(rt.worker.dead_letter)
                      for rt in cluster.runtimes.values())
    snap = cluster.control.snapshot()
    out = {
        "records": int(n),
        "quarantined": int(quarantined),
        "rows_loaded": int(pipe.warehouse.rows_loaded),
        "residual_lag": int(cluster._operational_lag()),
        "evictions": int(snap["evictions"]),
        "restarts": int(snap["restarts"]),
        "breaker_open": bool(snap["breaker_open"]),
        "poison_quarantined": bool(
            quarantined > 0
            and pipe.warehouse.rows_loaded == n - quarantined
            and cluster._operational_lag() == 0),
        "no_crash_loop": bool(snap["restarts"] == 0
                              and snap["evictions"] == 0
                              and not snap["breaker_open"]),
    }
    print(f"  poison: {quarantined} quarantined, "
          f"{out['rows_loaded']}/{n} clean rows loaded, "
          f"restarts={out['restarts']}, lag={out['residual_lag']}")
    return out


# ------------------------------------------------------------------- drivers
def summary(quick: bool = False) -> Dict[str, float]:
    """Small single-cycle figures for ``benchmarks.run``."""
    n = 1_500 if quick else 3_000
    det = bench_detection(n)
    poi = bench_poison(n)
    return {
        "detection_latency_s": det["latency_s"],
        "detection_within_bound": int(det["detection_within_bound"]),
        "byte_identical": int(det["byte_identical"]),
        "poison_quarantined": int(poi["poison_quarantined"]),
        "no_crash_loop": int(poi["no_crash_loop"]),
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized: small streams, one cycle per arm")
    ap.add_argument("--out", default="BENCH_control.json")
    args = ap.parse_known_args()[0]

    if args.smoke:
        n0, burst, n_det, n_poi = 4_000, 4_000, 2_500, 2_500
    elif args.quick:
        n0, burst, n_det, n_poi = 8_000, 8_000, 4_000, 4_000
    else:
        n0, burst, n_det, n_poi = 16_000, 16_000, 8_000, 8_000

    results = {
        "workload": {
            "n_partitions": N_PARTITIONS,
            "spike_master_key_space": n0, "spike_burst_records": burst,
            "detection_records": n_det, "poison_records": n_poi,
            "note": ("spike runs a live paced CDC feed through the real "
                     "ConcurrentCluster with the autonomous controller; "
                     "detection/poison run pre-extracted streams so a "
                     "byte-identity oracle exists — on the noisy shared "
                     "container only the ratios and boolean contracts "
                     "are meaningful (docs/BENCHMARKS.md)"),
        },
    }
    print("spike: paced stream -> burst -> autonomous scale-up -> recovery")
    results["spike"] = bench_spike(n0, burst)
    print("detection: hung stage -> supervised evict + restart")
    results["detection"] = bench_detection(n_det)
    print("poison: deterministic bad record -> dead-letter quarantine")
    results["poison"] = bench_poison(n_poi)

    sp, det, poi = results["spike"], results["detection"], results["poison"]
    results["gates"] = {
        "complete": bool(sp["complete"] and det["complete"]),
        "controller_acted": bool(sp["controller_acted"]),
        "spike_recovered": bool(sp["spike_recovered"]),
        "human_calls_zero": bool(sp["human_calls"] == 0
                                 and not sp["controller_crashed"]),
        "detection_within_bound": bool(det["detection_within_bound"]),
        "byte_identical": bool(det["byte_identical"]),
        "restart_ok": bool(det["restart_ok"]),
        "poison_quarantined": bool(poi["poison_quarantined"]),
        "no_crash_loop": bool(poi["no_crash_loop"]),
    }
    print("gates:", results["gates"])

    with open(args.out, "w") as f:
        json.dump(results, f, indent=2)
    print(f"wrote {args.out}")


if __name__ == "__main__":
    from repro.core.device import enable_compile_cache
    enable_compile_cache()
    main()
