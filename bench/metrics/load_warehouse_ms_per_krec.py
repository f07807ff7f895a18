"""Runtime layer: host milliseconds of the warehouse load (`load.warehouse`:
partition sort, the shared warehouse lock, commit, the serving delta
publish) per 1,000 records loaded in the window."""
from bench.harness import ms_per_k


def read(run):
    return ms_per_k(run, "load.warehouse", "records")
