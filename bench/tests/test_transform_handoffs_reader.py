"""The transform_handoffs_per_dispatch reader on a made-up run: the mean
`handoffs` of the `transform.dispatch` spans that start in the window,
None where no such span carries the argument."""
import pytest

from bench import harness
from bench.steelworks import Run

WINDOW = (10.0, 20.0)


def read(spans):
    mod = harness.load_module(
        harness.BENCH / "metrics" / "transform_handoffs_per_dispatch.py",
        "bench_metric_transform_handoffs_per_dispatch")
    return mod.read(Run(config={}, window=WINDOW, e2e={}, attempted=0,
                        failed=0, compared={}, notes={}, spans=spans))


def x(name, t0, dur, **args):
    return ("X", name, "w0.transform", t0, dur, args)


def test_transform_handoffs_per_dispatch():
    spans = [
        x("transform.dispatch", 11.0, 0.01, records=400, batch=0,
          handoffs=3),
        x("transform.dispatch", 12.0, 0.01, records=100, batch=3,
          handoffs=1),
        x("transform.dispatch", 9.0, 0.01, records=900, batch=7,
          handoffs=8),                                 # starts before
        x("transform.dispatch", 20.0, 0.01, records=900, batch=15,
          handoffs=8),                                 # starts at the end
        x("transform.launch", 12.0, 0.01, records=100, batch=3,
          handoffs=5),                                 # another span
    ]
    assert read(spans) == pytest.approx(2.0)
    assert read(spans[2:]) is None
    # a program whose dispatch spans carry no handoffs has nothing to read
    assert read([x("transform.dispatch", 11.0, 0.01, records=5,
                   batch=0)]) is None
