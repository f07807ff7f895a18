"""Kernels layer: the DimTrade transform program's share of its roofline.
The least bytes each call must move (records in, both probe windows, rows
out; ``bench.tpcdi_roofline``), over the chip's HBM bandwidth, against the
program's summed device time in the trace."""
from bench import tpcdi_roofline


def read(run):
    return tpcdi_roofline.dimtrade_share(run)
