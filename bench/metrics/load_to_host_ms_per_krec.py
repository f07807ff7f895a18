"""Device layer: host milliseconds of the load stage's blocking D2H sync
(`load.to_host`: `FactBlock.to_host` and the rollup) per 1,000 records
loaded in the window."""
from bench.harness import ms_per_k


def read(run):
    return ms_per_k(run, "load.to_host", "records")
