"""Worker-step layer: host milliseconds of the transform stage's launch
(`transform.launch`: batch packing, H2D, the jit call, the async D2H
start) per 1,000 records in the window."""
from bench.harness import ms_per_k


def read(run):
    return ms_per_k(run, "transform.launch", "records")
