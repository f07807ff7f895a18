"""Worker-step layer: host milliseconds of the transform stage's dispatch spans (cache snapshot, dispatch, no sync) per 1,000 records in the window."""
from bench.harness import ms_per_k


def read(run):
    return ms_per_k(run, "transform.dispatch", "records")
