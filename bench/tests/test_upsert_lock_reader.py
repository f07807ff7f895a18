"""The load_upsert_lock_ms_per_krec reader on a made-up run: the
``load.upsert_lock`` spans that start in the window count, one that starts
at its end does not, and a program without the span has nothing to read."""
import pytest

from bench import harness
from bench.steelworks import Run

WINDOW = (10.0, 20.0)


def read(spans):
    mod = harness.load_module(
        harness.BENCH / "metrics" / "load_upsert_lock_ms_per_krec.py",
        "bench_metric_load_upsert_lock_ms_per_krec")
    return mod.read(Run(config={}, window=WINDOW, e2e={}, attempted=0,
                        failed=0, compared={}, notes={}, spans=spans))


def x(name, t0, dur, **args):
    return ("X", name, "w0.load", t0, dur, args)


def test_upsert_lock_ms_per_krec():
    spans = [
        x("load.upsert", 11.0, 0.05, records=400, updated=200),
        x("load.upsert_lock", 11.0, 0.001, records=400),
        x("load.upsert_lock", 11.02, 0.003, records=100),   # a foreign shard
        x("load.upsert_lock", 20.0, 1.0, records=500),      # at the end
    ]
    # 4 ms over 500 records
    assert read(spans) == pytest.approx(8.0)
    # one span that starts at the window's end: nothing inside it
    assert read(spans[3:]) is None
    # a program without the span (the parent's) has nothing to read
    assert read(spans[:1]) is None
