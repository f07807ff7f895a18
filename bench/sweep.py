"""Many runs of one cell in one process, one JSON line each: for the
readings a limit is set from, for the control, and for the knee sweep.

    python3 bench/sweep.py --workload oee.backlog --seconds 20 --seeds 1 2 3
    python3 bench/sweep.py --workload oee.backlog --seconds 20 --seeds 1 2 3 --control
    python3 bench/sweep.py --workload oee.steady --seconds 10 --seeds 7 --rates 2000 4000 8000

Each run builds the deployment afresh from its seed; the process keeps JAX
and its compiled programs, so only the first run pays for start-up.
``--control`` puts the bfloat16 reference in the program's place in the
comparison (``steelworks.compare``): the numbers it prints under
``compared`` are the control's readings, and those of the program in the
same run are under ``notes.compared_sound``. ``--rates`` overrides the traffic's rate, once per
rate (a stream's rate, a backlog's fill rate), once per rate: the sweep
that finds a configuration's knee (the highest rate at which neither the
broker backlog nor the unfolded view backlog grows over the window), or
the backlog run that measures its drain rate.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--rates", type=float, nargs="*", default=[])
    args = ap.parse_args(argv)
    harness.prepare_process()
    spec = harness.load_cell(args.workload)
    try:
        devices = harness.require_chips(spec["cell"]["chips"])
    except harness.BenchError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    harness.enable_cache()
    peaks = harness.peaks_of(devices[0].device_kind)
    deployment = importlib.import_module(
        f"bench.{spec['config']['deployment']}")
    runs = [(seed, rate) for rate in (args.rates or [None])
            for seed in args.seeds]
    t_start = T_PROCESS
    for seed, rate in runs:
        key = ("rate_records_s" if spec["traffic"]["mode"] == "stream"
               else "fill_records_s")
        overrides = {key: rate} if rate else None
        with harness.quiet_stdout():
            run = deployment.run(spec, seed, args.seconds, False, t_start,
                                 peaks=peaks, control=args.control,
                                 overrides=overrides)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "rate": rate, "control": args.control,
                          "e2e": run.e2e, "attempted": run.attempted,
                          "failed": run.failed, "compared": run.compared,
                          "notes": run.notes}), flush=True)
        t_start = time.perf_counter()
    return 0


if __name__ == "__main__":
    sys.exit(main())
