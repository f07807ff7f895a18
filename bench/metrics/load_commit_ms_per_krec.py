"""Runtime layer: host milliseconds of the load stage's warehouse-load-and-commit spans per 1,000 records loaded in the window."""
from bench.harness import ms_per_k


def read(run):
    return ms_per_k(run, "load.commit", "records")
