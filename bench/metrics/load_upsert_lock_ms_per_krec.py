"""Runtime layer: host milliseconds the load stages wait for a keyed-target shard's lock (``load.upsert_lock``, inside ``load.upsert``) per 1,000 CDC records applied in the window. None where the program has no such span."""
from bench.harness import ms_per_k


def read(run):
    return ms_per_k(run, "load.upsert_lock", "records")
