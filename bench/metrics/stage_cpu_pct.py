"""Runtime layer: the share of the worker stage spans' wall time their
threads spent on the CPU: 100 x the summed `cpu_s` over the summed
duration of the `ingest.fetch`, `transform.dispatch` and `load.commit`
spans that start in the window. The rest is waiting: a lock, the
interpreter lock, the device, a queue."""
STAGES = ("ingest.fetch", "transform.dispatch", "load.commit")


def read(run):
    lo, hi = run.window
    wall = cpu = 0.0
    for ph, name, _lane, t0, dur, args in run.spans:
        if (ph == "X" and name in STAGES and lo <= t0 < hi
                and args and "cpu_s" in args):
            wall += dur
            cpu += args["cpu_s"]
    return 100.0 * cpu / wall if wall else None
