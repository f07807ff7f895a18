"""Serving layer: host milliseconds of the view engine's fold spans per 1,000 fact rows folded in the window."""
from bench.harness import ms_per_k


def read(run):
    return ms_per_k(run, "serving.fold", "rows")
