"""Sharded serving-plane scaling benchmark → ``BENCH_shard.json``.

The sharded warehouse/serving plane (``repro.runtime.shard_plane``)
splits every view's SEGMENT COLUMNS across shards: each shard folds the
full delta with foreign segments masked to the -1 identity, and the
segment-compacted fold (``_fold_blocks``) then does work proportional to
the shard's *owned active columns* — so K shards each fold ~1/K of the
columns and, on a real mesh, the cluster's fold wall is the max over
shards. Cross-shard reads pay one explicit merge (owner-gather for the
published front; pairwise tree reduce for the collective path).

Three arms per shard count K ∈ {1, 2, 4}:

* **modeled** — the deterministic unit-cost barrier model (the CI gate).
  Per fold block the compacted tree costs ``rows_pow2 × cols_pow2``
  elementwise ops; per delta the cluster cost is the max over shards of
  that unit cost, summed over deltas. Exactly reproducible (seeded
  workload, integer costs): with S a power of two and dense deltas the
  per-shard active-column count is exactly S/K, so the model exposes the
  plane's true parallel speedup with zero host noise.
* **measured** — each shard's masked fold executed SERIALLY on this
  host, walled individually; simulated parallel wall = max over shards
  (shards share nothing on the write path, so on a K-device mesh they
  run concurrently — max is the honest wall model). The merge
  (owner-gather of the [K, S, W] stack) is walled separately and
  reported as ``merge_overhead_fraction`` of the total read-side cost.
  Host-noise caveat: docs/BENCHMARKS.md.
* **parity** — booleans, no noise band: sharded-engine published fronts
  bitwise-identical to the single-device engine across every steelworks
  view; backend owner-gather == unsharded fold; tree reduce == owner
  gather; and (subprocess, 4 forced host devices) the REAL ``shard_map``
  mesh fold bitwise-identical to the single-device jax engine.

    PYTHONPATH=src python -m benchmarks.shard_scaling [--quick|--smoke]
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import textwrap
import time
from typing import Dict, List, Tuple

import numpy as np

from repro.core.backend import FOLD_BLOCK, available_backends, get_backend
from repro.runtime.shard_plane import (ShardedViewEngine, owner_gather,
                                       tree_reduce)
from repro.serving.engine import MaterializedViewEngine
from repro.serving.views import steelworks_views

SHARD_COUNTS = (1, 2, 4)


# ------------------------------------------------------------------ workload
def synth_deltas(n_deltas: int, rows: int, n_segments: int, n_lanes: int,
                 seed: int = 0) -> List[Tuple[np.ndarray, np.ndarray]]:
    """Dense KPI deltas: every row hits a uniform-random segment (the
    write-path regime sharding targets — every shard busy every delta)."""
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(n_deltas):
        seg = rng.integers(0, n_segments, rows).astype(np.int64)
        vals = rng.uniform(0.0, 100.0, (rows, n_lanes)).astype(np.float32)
        out.append((seg, vals))
    return out


def _static_owners(n_segments: int, k: int) -> np.ndarray:
    return (np.arange(n_segments, dtype=np.int64) * k) // n_segments


# ------------------------------------------------------------------- modeled
def _pow2(x: int) -> int:
    p = 1
    while p < x:
        p *= 2
    return p


def _block_cost(seg: np.ndarray, n_segments: int,
                owned: np.ndarray = None) -> int:
    """Unit cost of one shard's compacted fold over one delta: per
    FOLD_BLOCK chunk, rows padded to a power of two times active columns
    padded to a power of two (>= 8, capped at n_segments) — exactly the
    tree shape ``_fold_blocks`` executes."""
    cost = 0
    for i in range(0, len(seg), FOLD_BLOCK):
        blk = seg[i:i + FOLD_BLOCK]
        live = np.unique(blk[(blk >= 0) & (blk < n_segments)])
        if owned is not None:
            live = live[owned[live]]
        if not len(live):
            continue
        cost += _pow2(len(blk)) * min(max(_pow2(len(live)), 8), n_segments)
    return cost


def run_modeled(deltas, n_segments: int) -> Dict:
    """Deterministic barrier model: cluster cost per delta = max over
    shards; speedup(K) = single-device cost / sharded cluster cost."""
    single = sum(_block_cost(seg, n_segments) for seg, _ in deltas)
    out = {"single_cost": single, "speedup": {}, "cluster_cost": {}}
    for k in SHARD_COUNTS:
        owners = _static_owners(n_segments, k)
        cluster = 0
        for seg, _ in deltas:
            cluster += max(_block_cost(seg, n_segments, owners == sh)
                           for sh in range(k))
        out["cluster_cost"][str(k)] = cluster
        out["speedup"][str(k)] = round(single / cluster, 3) if cluster else 0
    return out


# ------------------------------------------------------------------ measured
def run_measured(deltas, n_segments: int, repeats: int = 3) -> Dict:
    """Serial per-shard folds, walled individually; simulated parallel
    wall = Σ_deltas max_shard wall. Medians over ``repeats`` interleaved
    passes (all arms timed in the same pass — paired, like every other
    benchmark here)."""
    be = get_backend("numpy")
    samples = {str(k): [] for k in SHARD_COUNTS}
    merge_samples, single_samples = [], []
    parity_backend = True
    for _ in range(repeats):
        t0 = time.perf_counter()
        ref = None
        for seg, vals in deltas:
            ref = be.fold_segments(seg, vals, n_segments)
        single_samples.append(time.perf_counter() - t0)
        for k in SHARD_COUNTS:
            owners = _static_owners(n_segments, k)
            wall = 0.0
            tables = []
            for seg, vals in deltas:
                shard_walls, shard_tables = [], []
                for sh in range(k):
                    masked = np.where(
                        (seg >= 0) & (seg < n_segments)
                        & (owners[np.clip(seg, 0, n_segments - 1)] == sh),
                        seg, np.int64(-1))
                    t0 = time.perf_counter()
                    shard_tables.append(
                        be.fold_segments(masked, vals, n_segments))
                    shard_walls.append(time.perf_counter() - t0)
                wall += max(shard_walls)
                tables = shard_tables
            samples[str(k)].append(wall)
            if k == max(SHARD_COUNTS):
                t0 = time.perf_counter()
                merged = owner_gather(tables, owners)
                merge_samples.append(time.perf_counter() - t0)
                parity_backend &= merged.tobytes() == ref.tobytes()
    med = lambda xs: sorted(xs)[len(xs) // 2]  # noqa: E731
    single = med(single_samples)
    walls = {k: med(v) for k, v in samples.items()}
    merge = med(merge_samples)
    kmax = str(max(SHARD_COUNTS))
    return {
        "single_wall_s": round(single, 4),
        "parallel_wall_s": {k: round(w, 4) for k, w in walls.items()},
        "speedup": {k: round(single / w, 3) if w else 0
                    for k, w in walls.items()},
        "merge_wall_s": round(merge, 5),
        "merge_overhead_fraction": round(merge / (walls[kmax] + merge), 4)
        if walls[kmax] + merge else 0.0,
        "parity_backend_bitwise": bool(parity_backend),
    }


# -------------------------------------------------------------------- parity
def _mk_facts(rng, n: int, n_units: int) -> np.ndarray:
    f = np.zeros((n, 10), np.float32)
    f[:, 0] = rng.integers(0, n_units, n)
    f[:, 1] = rng.uniform(0, 10_000, n)
    f[:, 2] = f[:, 1] + rng.uniform(1, 50, n)
    f[:, 3:7] = rng.uniform(0, 1, (n, 4))
    f[:, 7] = rng.uniform(0, 40, n)
    f[:, 8] = rng.uniform(0, 10, n)
    f[:, 9] = (rng.uniform(0, 1, n) > 0.1).astype(np.float32)
    return f


def run_engine_parity(n_units: int = 32, n_deltas: int = 5,
                      rows: int = 1_500) -> Dict:
    """ShardedViewEngine on every shard count vs the plain engine, same
    synthetic fact stream: published fronts must be bitwise-identical
    and the tree-reduce path must match the owner-gather front."""
    rng = np.random.default_rng(7)
    stream = [_mk_facts(rng, rows, n_units) for _ in range(n_deltas)]
    specs = steelworks_views(n_units)
    ref = MaterializedViewEngine(specs, backend="numpy")
    for d in stream:
        ref.publish(d)
    ref.fold_pending()
    want = {s.name: ref.snapshot().view(s.name).table.tobytes()
            for s in specs}
    parity = tree_ok = True
    for k in SHARD_COUNTS:
        eng = ShardedViewEngine(specs, n_shards=k, backend="numpy")
        for d in stream:
            eng.publish(d)
        eng.fold_pending()
        snap = eng.snapshot()
        for s in specs:
            parity &= snap.view(s.name).table.tobytes() == want[s.name]
            tree_ok &= eng.tree_reduced_table(s.name).tobytes() \
                == want[s.name]
    return {"parity_engine_bitwise": bool(parity),
            "tree_reduce_bitwise": bool(tree_ok)}


_MESH_DRILL = textwrap.dedent("""
    import numpy as np
    from repro.launch.mesh import virtual_devices, make_shard_mesh
    virtual_devices(4)
    import jax
    from repro.core.backend import get_backend
    from repro.runtime.shard_plane import ShardedViewEngine
    from repro.serving.engine import MaterializedViewEngine
    from repro.serving.views import steelworks_views

    rng = np.random.default_rng(11)
    n_units = 16
    specs = steelworks_views(n_units)

    def mk(n):
        f = np.zeros((n, 10), np.float32)
        f[:, 0] = rng.integers(0, n_units, n)
        f[:, 1] = rng.uniform(0, 10000, n)
        f[:, 2] = f[:, 1] + rng.uniform(1, 50, n)
        f[:, 3:7] = rng.uniform(0, 1, (n, 4))
        f[:, 7] = rng.uniform(0, 40, n)
        f[:, 8] = rng.uniform(0, 10, n)
        f[:, 9] = 1.0
        return f

    be = get_backend("jax")
    eng = ShardedViewEngine(specs, n_shards=4, backend="jax")
    ref = MaterializedViewEngine(specs, backend="jax")
    be.set_mesh(make_shard_mesh(4))
    try:
        for _ in range(4):
            d = mk(int(rng.integers(200, 3000)))
            eng.publish(d); ref.publish(d)
            eng.fold_pending(); ref.fold_pending()
    finally:
        be.set_mesh(None)
    s, r = eng.snapshot(), ref.snapshot()
    ok = all(s.view(sp.name).table.tobytes()
             == r.view(sp.name).table.tobytes() for sp in specs)
    print("MESH_PARITY", "OK" if ok else "FAIL", jax.device_count())
""")


def run_mesh_drill(timeout_s: int = 600) -> Dict:
    """The REAL thing: a subprocess with 4 forced host devices folds via
    ``shard_map`` on an actual 4-device mesh and must stay bitwise equal
    to the single-device jax engine. Subprocess because device count
    binds at jax initialization (this process is already initialized)."""
    if "jax" not in available_backends():
        return {"mesh_parity": False, "skipped": "jax unavailable"}
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["JAX_PLATFORMS"] = "cpu"
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env["PYTHONPATH"] = os.path.abspath(src) + os.pathsep \
        + env.get("PYTHONPATH", "")
    out = subprocess.run([sys.executable, "-c", _MESH_DRILL], env=env,
                         capture_output=True, text=True, timeout=timeout_s)
    ok = out.returncode == 0 and "MESH_PARITY OK" in out.stdout
    res = {"mesh_parity": bool(ok)}
    if not ok:
        res["stderr_tail"] = out.stderr[-1500:]
    return res


# ---------------------------------------------------------------------- main
def _gates(modeled: Dict, measured: Dict, parity: Dict,
           mesh: Dict) -> Dict:
    return {
        "parity_engine_bitwise": parity["parity_engine_bitwise"],
        "tree_reduce_bitwise": parity["tree_reduce_bitwise"],
        "parity_backend_bitwise": measured["parity_backend_bitwise"],
        "mesh_parity": mesh["mesh_parity"],
        "speedup_modeled_2": modeled["speedup"]["2"],
        "speedup_modeled_4": modeled["speedup"]["4"],
        "merge_overhead_fraction": measured["merge_overhead_fraction"],
        "complete": True,
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized: small S, 1 repeat")
    ap.add_argument("--skip-mesh", action="store_true",
                    help="skip the 4-device subprocess drill")
    ap.add_argument("--out", default="BENCH_shard.json")
    args = ap.parse_known_args()[0]

    if args.smoke:
        S, rows, n_deltas, repeats = 512, 2_048, 3, 1
    elif args.quick:
        S, rows, n_deltas, repeats = 1_024, 4_096, 4, 3
    else:
        S, rows, n_deltas, repeats = 1_024, 8_192, 6, 5
    n_lanes = 4
    deltas = synth_deltas(n_deltas, rows, S, n_lanes)

    results = {
        "workload": {
            "n_segments": S, "rows_per_delta": rows, "n_deltas": n_deltas,
            "n_lanes": n_lanes, "repeats": repeats,
            "shard_counts": list(SHARD_COUNTS),
            "note": ("modeled = deterministic unit-cost barrier model "
                     "(rows_pow2 x owned_active_cols_pow2 per block, "
                     "cluster cost = max over shards) — the CI gate; "
                     "measured = serial per-shard folds on THIS host, "
                     "parallel wall simulated as max over shards "
                     "(docs/BENCHMARKS.md caveat applies)"),
        }
    }
    results["modeled"] = run_modeled(deltas, S)
    print(f"modeled speedup: {results['modeled']['speedup']}")
    results["measured"] = run_measured(deltas, S, repeats)
    print(f"measured (simulated-parallel) speedup: "
          f"{results['measured']['speedup']}, merge overhead "
          f"{results['measured']['merge_overhead_fraction']}")
    results["parity"] = run_engine_parity()
    mesh = {"mesh_parity": False, "skipped": "--skip-mesh"} \
        if args.skip_mesh else run_mesh_drill()
    results["mesh"] = mesh
    print(f"parity: {results['parity']}, mesh: {mesh}")

    results["gates"] = _gates(results["modeled"], results["measured"],
                              results["parity"], mesh)
    print("gates:", results["gates"])
    with open(args.out, "w") as f:
        json.dump(results, f, indent=2)
    print(f"wrote {args.out}")


def summary(quick: bool = False) -> Dict:
    """Small figures for ``benchmarks.run``."""
    S, rows, n_deltas = (512, 2_048, 3) if quick else (1_024, 4_096, 4)
    deltas = synth_deltas(n_deltas, rows, S, 4)
    modeled = run_modeled(deltas, S)
    parity = run_engine_parity()
    return {
        "speedup_modeled_2": modeled["speedup"]["2"],
        "speedup_modeled_4": modeled["speedup"]["4"],
        "parity_engine_bitwise": int(parity["parity_engine_bitwise"]),
        "tree_reduce_bitwise": int(parity["tree_reduce_bitwise"]),
    }


if __name__ == "__main__":
    from repro.core.device import enable_compile_cache
    enable_compile_cache()
    main()
