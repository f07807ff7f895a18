"""Crash-recovery benchmark → ``BENCH_recovery.json``.

Three questions about the durability layer (ARCHITECTURE.md "Durability &
recovery"), each with a CI-gated answer:

* **Scaling** — recovery work must be O(suffix since the last
  checkpoint), never O(history). Histories of growing length are run
  with a FIXED checkpoint cadence, then recovered from the journal; the
  serving replay suffix (``replayed_chunks``) stays bounded while the
  chunk log grows, so the replay *fraction* falls — the structural
  sublinearity gate. Wall-clock recovery is compared against the cold
  alternative (re-extract + re-transform + re-fold the whole stream from
  the CDC log): ``recovery_speedup_vs_cold`` grows with history and
  gates as a host-relative paired ratio.
* **Overhead** — what the periodic checkpointer costs a sustained
  concurrent run: paired cycles of the same workload through the real
  ``ConcurrentCluster`` with and without a ``checkpoint_every_s``
  thread, adjacent in time; the gate is the median paired wall ratio
  (with / without — lower is better).
* **Kill -9** — the real thing, not an in-process analogue: a child
  process runs the pipeline with a ``mode="sigkill"`` injector armed at
  the load/commit seam and is destroyed by the kernel mid-stream; the
  parent recovers from the journal the corpse left behind, finishes the
  stream, and verifies the warehouse is byte-identical to an
  uninterrupted oracle — exactly-once through an actual SIGKILL.

    PYTHONPATH=src python -m benchmarks.recovery_bench [--quick|--smoke]
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import signal
import subprocess
import sys
import tempfile
import time
from typing import Dict, Optional

import numpy as np

from repro.configs.dod_etl import steelworks_config
from repro.core import DODETLPipeline, SourceDatabase
from repro.data.sampler import SamplerConfig, SteelworksSampler
from repro.durability import (DurabilityJournal, FaultInjector,
                              InjectedCrash, RecoveryCoordinator,
                              recover_pipeline)
from repro.durability.faults import LOAD_PRE_COMMIT
from repro.runtime.cluster import ConcurrentCluster
from repro.serving.engine import MaterializedViewEngine
from repro.serving.views import steelworks_views

N_PARTITIONS = 8
N_WORKERS = 2
SEED = 7


def build(n: int, seed: int = SEED, fault=None, join_depth=1):
    cfg = steelworks_config(n_partitions=N_PARTITIONS, backend="numpy")
    cfg = dataclasses.replace(cfg, buffer_capacity=65536)
    src = SourceDatabase()
    sampler = SteelworksSampler(cfg, SamplerConfig(
        records_per_table=n, n_equipment=N_PARTITIONS,
        late_master_frac=0.1, seed=seed))
    sampler.generate(src)
    pipe = DODETLPipeline(cfg, src, n_workers=N_WORKERS, fault=fault,
                          join_depth=join_depth)
    eng = MaterializedViewEngine(steelworks_views(cfg.n_business_keys),
                                 backend="numpy")
    pipe.warehouse.attach_serving(eng)
    return cfg, src, pipe, eng, sampler


def drive(pipe, eng, coord=None, ckpt_every=4, extract_per=400, cap=200,
          max_steps=2000):
    """Deterministic incremental loop (the test-suite drill loop at
    benchmark scale): extract a slice, one micro-batch step, fold views,
    checkpoint on cadence."""
    steps = stalls = 0
    log = pipe.source.log
    while steps < max_steps:
        steps += 1
        pipe.extract(extract_per)
        n = pipe.step(cap)
        eng.fold_pending()
        if coord is not None and steps % ckpt_every == 0:
            coord.checkpoint(pipe, engine=eng)
        lag = sum(max(0, log.next_lsn - l.offset)
                  for l in pipe.tracker.listeners)
        if lag > 0:
            stalls = 0
            continue
        if n == 0 and sum(len(w.buffer) for w in pipe.workers) == 0:
            break
        stalls = stalls + 1 if n == 0 else 0
        if stalls >= 3:
            break
    return steps


# ------------------------------------------------------------------- scaling
def bench_scaling(histories, suffix: int = 300, ckpt_every: int = 4) -> Dict:
    """Recovery cost vs history length with a FIXED un-checkpointed
    suffix: the checkpointed prefix grows, the post-checkpoint tail (the
    crash window recovery must re-process from the CDC log) stays
    constant. Recovery = journal restore + re-process the suffix; the
    cold alternative re-processes the WHOLE stream. Sublinearity shows
    up as the recovery/cold gap widening with history.

    Read the wall-clock ratio with care: this repo's synthetic transform
    is deliberately cheap and fully vectorized, so re-PROCESSING a
    record costs about the same as DECODING its journaled bytes — the
    ratio hovers near 1 and mostly measures npz decode speed. The
    architectural claim is the structural one (gated): re-processed work
    is bounded by the checkpoint gap while cold replay grows with
    history — with a production-cost transform the wall ratio follows.
    """
    jd = 1
    rows = []
    for n in histories:
        # phase 1: checkpointed prefix; phase 2: suffix after the last
        # checkpoint — production events the journal never saw
        cfg, src, pipe, eng, sampler = build(n, join_depth=jd)
        with tempfile.TemporaryDirectory() as root:
            coord = RecoveryCoordinator(DurabilityJournal(root))
            drive(pipe, eng, coord=coord, ckpt_every=ckpt_every)
            coord.checkpoint(pipe, engine=eng)       # last durable point
            sampler.generate(src, n_per_table=suffix,
                             tables=("production",))
            drive(pipe, eng)
            want = pipe.warehouse.canonical_fact_table().tobytes()
            seq = pipe.warehouse.commit_seq

            t0 = time.perf_counter()
            eng2 = MaterializedViewEngine(
                steelworks_views(cfg.n_business_keys), backend="numpy")
            pipe2, coord2, info = recover_pipeline(
                cfg, src, DurabilityJournal(root), engine=eng2,
                join_depth=jd)
            rows_at_restore = pipe2.warehouse.rows_loaded
            drive(pipe2, eng2)                       # re-process the tail
            t_recover = time.perf_counter() - t0
            assert info is not None
            assert pipe2.warehouse.canonical_fact_table().tobytes() == want

        # the cold alternative: no journal — re-run the whole stream
        cfg3, src3, pipe3, eng3, sampler3 = build(n, join_depth=jd)
        sampler3.generate(src3, n_per_table=suffix, tables=("production",))
        t0 = time.perf_counter()
        drive(pipe3, eng3)
        t_cold = time.perf_counter() - t0
        assert pipe3.warehouse.canonical_fact_table().tobytes() == want

        reproc = pipe2.warehouse.rows_loaded - rows_at_restore
        rows.append({
            "history_records": int(n * 3 + suffix),
            "commit_seq": int(seq),
            "restored_commit_seq": int(info["commit_seq"]),
            "reprocessed_rows": int(reproc),
            "reprocessed_fraction": round(
                reproc / pipe2.warehouse.rows_loaded, 4),
            "recover_wall_s": round(t_recover, 4),
            "cold_replay_wall_s": round(t_cold, 4),
            "speedup_vs_cold": round(t_cold / max(t_recover, 1e-9), 2),
        })
        print(f"  history {rows[-1]['history_records']}: recover+finish "
              f"{t_recover*1e3:.1f} ms (re-processed {reproc} of "
              f"{pipe2.warehouse.rows_loaded} rows), cold "
              f"{t_cold*1e3:.1f} ms -> {rows[-1]['speedup_vs_cold']}x")
    first, last = rows[0], rows[-1]
    return {
        "per_history": rows,
        "suffix_records": suffix,
        "ckpt_every_steps": ckpt_every,
        # structural sublinearity: re-processed work is set by the
        # checkpoint gap, not history length — its fraction of the
        # warehouse must FALL as the history grows
        "sublinear_ok": bool(
            last["reprocessed_fraction"] < 0.5
            and last["reprocessed_fraction"] < first["reprocessed_fraction"]
            and last["speedup_vs_cold"] >= first["speedup_vs_cold"]),
        "recovery_speedup_vs_cold": last["speedup_vs_cold"],
    }


# ------------------------------------------------------------------ overhead
def bench_overhead(n: int, cycles: int, every_s: float = 0.05) -> Dict:
    """Paired sustained cycles through the real concurrent runtime, with
    and without the periodic checkpointer thread."""
    ratios, walls = [], []
    steps_ckpt = 0
    for _ in range(cycles):
        pair = {}
        for arm in ("off", "on"):
            cfg, src, pipe, eng, _ = build(n)
            pipe.extract()
            root_ctx = tempfile.TemporaryDirectory()
            with root_ctx as root:
                coord = (RecoveryCoordinator(DurabilityJournal(root))
                         if arm == "on" else None)
                cluster = ConcurrentCluster(
                    pipe, max_records_per_partition=200, poll_cdc=False,
                    serving=eng, recovery=coord,
                    checkpoint_every_s=every_s if arm == "on" else None)
                t0 = time.perf_counter()
                cluster.start()
                cluster.run_until_idle(timeout=300)
                cluster.stop_all()
                pair[arm] = time.perf_counter() - t0
                if arm == "on":
                    steps_ckpt = len(coord.journal.steps())
                assert pipe.warehouse.rows_loaded == n
        ratios.append(pair["on"] / max(pair["off"], 1e-9))
        walls.append(pair)
    mid = sorted(range(cycles), key=lambda i: ratios[i])[cycles // 2]
    return {
        "records": int(n),
        "checkpoint_every_s": every_s,
        "journal_steps_written": int(steps_ckpt),
        "paired_wall_s": [{k: round(v, 4) for k, v in p.items()}
                          for p in walls],
        "paired_ratios": [round(r, 3) for r in ratios],
        "checkpoint_overhead_ratio": round(ratios[mid], 3),
    }


# -------------------------------------------------------------------- kill -9
def _child(root: str, n: int) -> None:
    """Child half of the kill-9 drill: run with a SIGKILL injector armed
    at the load/commit seam. This function does not return."""
    fault = FaultInjector({LOAD_PRE_COMMIT: 5}, mode="sigkill")
    cfg, src, pipe, eng, _ = build(n, fault=fault)
    coord = RecoveryCoordinator(DurabilityJournal(root, fault=fault))
    drive(pipe, eng, coord=coord, ckpt_every=2, extract_per=150, cap=60)
    # reaching here means the seam was never hit — fail loudly, not -9
    sys.exit(3)


def bench_kill9(n: int) -> Dict:
    """SIGKILL a child pipeline mid-stream, recover from its journal in
    the parent, verify exactly-once byte identity vs an oracle."""
    with tempfile.TemporaryDirectory() as root:
        env = dict(os.environ)
        src_dir = os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "src")
        env["PYTHONPATH"] = src_dir + os.pathsep + env.get("PYTHONPATH", "")
        t0 = time.perf_counter()
        proc = subprocess.run(
            [sys.executable, "-m", "benchmarks.recovery_bench",
             "--child-kill9", root, "--n", str(n)],
            env=env, cwd=os.path.dirname(src_dir), capture_output=True,
            timeout=300)
        t_child = time.perf_counter() - t0
        killed = proc.returncode == -signal.SIGKILL
        steps_left = len(DurabilityJournal(root).steps())

        t0 = time.perf_counter()
        cfg, src, _, _, _ = build(n)
        eng2 = MaterializedViewEngine(steelworks_views(cfg.n_business_keys),
                                      backend="numpy")
        pipe2, coord2, info = recover_pipeline(
            cfg, src, DurabilityJournal(root), engine=eng2,
            n_workers=N_WORKERS)
        drive(pipe2, eng2, coord=coord2, ckpt_every=2, extract_per=150,
              cap=60)
        t_recover = time.perf_counter() - t0

    cfg_o, src_o, pipe_o, eng_o, _ = build(n)
    drive(pipe_o, eng_o, ckpt_every=2, extract_per=150, cap=60)
    identical = (pipe2.warehouse.canonical_fact_table().tobytes()
                 == pipe_o.warehouse.canonical_fact_table().tobytes())
    views_ok = all(
        eng2.snapshot().states[name].table.tobytes()
        == st.table.tobytes()
        for name, st in eng_o.snapshot().states.items())
    out = {
        "records": int(n),
        "child_killed_by_sigkill": bool(killed),
        "child_wall_s": round(t_child, 3),
        "journal_steps_survived": int(steps_left),
        "recovered_from_step": (None if info is None
                                else int(info["step"])),
        "recover_and_finish_wall_s": round(t_recover, 3),
        "rows_after_recovery": int(pipe2.warehouse.rows_loaded),
        "rows_expected": int(n),
        "kill9_exactly_once": bool(
            killed and identical and views_ok
            and pipe2.warehouse.rows_loaded == n),
    }
    print(f"  kill -9: child rc={proc.returncode}, "
          f"{steps_left} journal steps survived, recovered+finished in "
          f"{t_recover:.2f}s, exactly_once={out['kill9_exactly_once']}")
    return out


# ------------------------------------------------------------------- drivers
def summary(quick: bool = False) -> Dict[str, float]:
    """Small single-cycle figures for ``benchmarks.run``."""
    n = 1_000 if quick else 3_000
    scaling = bench_scaling([n // 2, n], ckpt_every=4)
    kill9 = bench_kill9(n // 2)
    return {
        "recover_wall_s": scaling["per_history"][-1]["recover_wall_s"],
        "reprocessed_fraction":
            scaling["per_history"][-1]["reprocessed_fraction"],
        "speedup_vs_cold": scaling["recovery_speedup_vs_cold"],
        "sublinear_ok": int(scaling["sublinear_ok"]),
        "kill9_exactly_once": int(kill9["kill9_exactly_once"]),
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized: short histories, 1 overhead cycle")
    ap.add_argument("--out", default="BENCH_recovery.json")
    ap.add_argument("--child-kill9", metavar="JOURNAL_DIR",
                    help=argparse.SUPPRESS)   # internal: kill-9 child half
    ap.add_argument("--n", type=int, default=1_500)
    args = ap.parse_known_args()[0]
    if args.child_kill9:
        _child(args.child_kill9, args.n)
        return

    if args.smoke:
        histories, overhead_n, cycles, kill_n = [500, 1_000, 2_000], \
            2_000, 1, 1_000
    elif args.quick:
        histories, overhead_n, cycles, kill_n = [1_000, 2_000, 4_000], \
            4_000, 3, 2_000
    else:
        histories, overhead_n, cycles, kill_n = \
            [2_000, 4_000, 8_000, 16_000], 8_000, 3, 4_000

    results = {
        "workload": {
            "n_partitions": N_PARTITIONS, "n_workers": N_WORKERS,
            "histories_per_table": histories, "overhead_records": overhead_n,
            "overhead_cycles": cycles, "kill9_records": kill_n,
            "note": ("scaling/kill9 run the deterministic sequential "
                     "drill loop; overhead runs the real "
                     "ConcurrentCluster with a periodic checkpointer — "
                     "on the noisy shared container only the paired "
                     "ratios are meaningful (docs/BENCHMARKS.md)"),
        },
    }
    print("scaling: recovery wall vs history (fixed checkpoint cadence)")
    results["scaling"] = bench_scaling(histories)
    print("overhead: paired cycles with/without the checkpointer")
    results["overhead"] = bench_overhead(overhead_n, cycles)
    print(f"overhead ratio (with/without): "
          f"{results['overhead']['checkpoint_overhead_ratio']}")
    print("kill -9: child SIGKILL mid-stream, parent recovers")
    results["kill9"] = bench_kill9(kill_n)

    results["gates"] = {
        "complete": bool(
            results["kill9"]["rows_after_recovery"]
            == results["kill9"]["rows_expected"]),
        "byte_identical": bool(results["kill9"]["kill9_exactly_once"]),
        "kill9_exactly_once": bool(results["kill9"]["kill9_exactly_once"]),
        "sublinear_ok": bool(results["scaling"]["sublinear_ok"]),
    }
    print("gates:", results["gates"])

    with open(args.out, "w") as f:
        json.dump(results, f, indent=2)
    print(f"wrote {args.out}")


if __name__ == "__main__":
    from repro.core.device import enable_compile_cache
    enable_compile_cache()
    main()
