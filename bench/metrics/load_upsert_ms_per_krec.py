"""Runtime layer: host milliseconds of the load stage's keyed-upsert spans (``load.upsert``) per 1,000 CDC records applied in the window."""
from bench.harness import ms_per_k


def read(run):
    return ms_per_k(run, "load.upsert", "records")
