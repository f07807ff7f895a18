"""Generator layer: 99th percentile of how late the open-loop generator
made its appends in the window (append stamp minus due time). A starved
generator understates every latency; this says by how much."""
import numpy as np


def read(run):
    late = run.gen_lateness_s
    if late is None or not len(late):
        return None
    return float(np.percentile(late, 99) * 1e3)
