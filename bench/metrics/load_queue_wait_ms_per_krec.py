"""Runtime layer: milliseconds transformed blocks waited between the
transform stage's hand-off and the load stage taking them (`load.queue_wait`
spans, a full queue included) per 1,000 records in the window."""
from bench.harness import ms_per_k


def read(run):
    return ms_per_k(run, "load.queue_wait", "records")
