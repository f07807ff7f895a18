"""Device layer: share of the traced window in which no operation ran on
the chip (1 - the union of device op intervals over the window)."""
from bench.harness import idle_pct


def read(run):
    return idle_pct(run)
