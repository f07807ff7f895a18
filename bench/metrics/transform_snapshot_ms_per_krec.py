"""Worker-step layer: host milliseconds of the transform stage's cache
snapshot (`transform.snapshot`: the cache lock, both snapshots, any
re-upload) per 1,000 records in the window."""
from bench.harness import ms_per_k


def read(run):
    return ms_per_k(run, "transform.snapshot", "records")
