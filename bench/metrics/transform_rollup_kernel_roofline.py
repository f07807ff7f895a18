"""Kernels layer: the fused transform+rollup program's share of its
roofline. The least bytes each call must move, counted from its shapes
(``bench.roofline``), over the chip's HBM bandwidth, against the program's
summed device time in the trace. Probing is gather-bound, so bandwidth,
not arithmetic, bounds it."""
from bench import roofline


def read(run):
    return roofline.transform_share(run)
