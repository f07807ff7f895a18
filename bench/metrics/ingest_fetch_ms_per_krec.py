"""Runtime layer: host milliseconds of the ingest stage's broker fetch spans per 1,000 records fetched in the window."""
from bench.harness import ms_per_k


def read(run):
    return ms_per_k(run, "ingest.fetch", "records")
