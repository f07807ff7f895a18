"""Runtime layer: milliseconds fetched batches waited between the ingest
stage's hand-off and the transform stage taking them (`transform.queue_wait`
spans, a full queue included) per 1,000 records in the window."""
from bench.harness import ms_per_k


def read(run):
    return ms_per_k(run, "transform.queue_wait", "records")
