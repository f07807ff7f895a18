"""Run one benchmark cell once, in this process, on the chips of this
machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

With ``--trace 0`` the result's metrics are the cell's end-to-end metrics;
with ``--trace 1`` the run is traced (stage spans and the profiler's device
trace) and the metrics are its per-layer metrics. The last line of standard
output is the result as one JSON object; the numbers that decide
``correct`` are printed beside their limits as the last lines of standard
error, and again under ``compared``, the result's last key. Exits non-zero
with no result when JAX's platform is not a TPU, when the cell asks for
more chips than there are, or when the run cannot be measured.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import harness  # noqa: E402


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             require_tpu: bool = True, t_process: float = T_PROCESS,
             **kw) -> str:
    """One run; returns the result line. ``require_tpu=False`` and the
    keyword arguments of the deployment's ``run`` are for the harness's
    own tests."""
    harness.prepare_process()
    spec = harness.load_cell(workload)
    cell, config, bench = spec["cell"], spec["config"], spec["bench"]
    import jax
    if require_tpu:
        devices = harness.require_chips(cell["chips"])
        harness.enable_cache()
        peaks = harness.peaks_of(devices[0].device_kind)
    else:
        devices = jax.devices()
        peaks = kw.pop("peaks", None)
    compiles = harness.CompileLog()
    deployment = importlib.import_module(f"bench.{config['deployment']}")
    with harness.quiet_stdout():
        run = deployment.run(spec, seed, seconds, trace, t_process,
                             peaks=peaks,
                             profiler_factory=harness.Profiler, **kw)
    if run.notes.get("backlog_left", 1) <= 0:
        raise harness.BenchError("the backlog emptied before the window "
                                 "ended: the traffic must fill more records")
    device = harness.device_info(devices, cell["chips"])
    device["memory_peak_bytes"] = run.notes.pop("memory_peak_bytes",
                                                device["memory_peak_bytes"])
    lo, hi = run.window
    run.notes["programs_setup"] = compiles.count("obtained", hi=lo)
    run.notes["compiles_setup"] = compiles.count("misses", hi=lo)
    run.notes["programs_window"] = compiles.count("obtained", lo, hi)
    breakdown = None
    if trace:
        metrics = harness.read_per_layer(bench, workload, run)
        if run.device_trace is not None:
            device["busy_s"] = run.device_trace["busy_s"]
            device["window_s"] = run.device_trace["window_s"]
            breakdown = {"device_ops": run.device_trace["device_ops"],
                         "idle_gaps": run.device_trace["idle_gaps"]}
    else:
        metrics = {}
        for m in harness.metrics_of(bench, workload, "end_to_end"):
            if m["name"] in run.e2e:
                metrics[m["name"]] = {"value": run.e2e[m["name"]],
                                      "unit": m["unit"]}
    compared = {name: {"value": value,
                       "limit": harness.limit_of(name, config)}
                for name, value in run.compared.items()}
    correct = all(c["value"] <= c["limit"] for c in compared.values())
    print(f"notes {run.notes}", file=sys.stderr)
    harness.print_compared(compared)
    return harness.result_line(correct, run.attempted, run.failed, metrics,
                               device, compared, breakdown)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        line = run_cell(args.workload, args.seed, args.seconds,
                        bool(args.trace))
    except harness.BenchError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
