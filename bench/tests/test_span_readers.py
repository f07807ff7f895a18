"""The worker-step span readers on a made-up run: spans that start in the
window count, spans that start before or at its end do not, and a reader
whose spans are absent returns None."""
import pytest

from bench import harness
from bench.steelworks import Run

WINDOW = (10.0, 20.0)


def reader(metric):
    return harness.load_module(
        harness.BENCH / "metrics" / f"{metric}.py",
        f"bench_metric_{metric}").read


def run_of(spans):
    return Run(config={}, window=WINDOW, e2e={}, attempted=0, failed=0,
               compared={}, notes={}, spans=spans)


def x(name, t0, dur, **args):
    return ("X", name, "w0.load", t0, dur, args)


@pytest.mark.parametrize("metric,span", [
    ("transform_queue_wait_ms_per_krec", "transform.queue_wait"),
    ("load_queue_wait_ms_per_krec", "load.queue_wait"),
    ("transform_snapshot_ms_per_krec", "transform.snapshot"),
    ("transform_launch_ms_per_krec", "transform.launch"),
    ("load_to_host_ms_per_krec", "load.to_host"),
    ("load_warehouse_ms_per_krec", "load.warehouse"),
])
def test_ms_per_krec_readers(metric, span):
    spans = [
        x(span, 11.0, 0.002, records=1000, batch=0),
        x(span, 12.0, 0.004, records=3000, batch=1),
        x(span, 9.5, 1.0, records=1000, batch=2),     # starts before
        x(span, 20.0, 1.0, records=1000, batch=3),    # starts at the end
        x("ingest.fetch", 13.0, 5.0, records=10),     # another span
    ]
    # 6 ms over 4,000 records
    assert reader(metric)(run_of(spans)) == pytest.approx(1.5)
    assert reader(metric)(run_of(spans[4:])) is None


def test_stage_cpu_pct():
    read = reader("stage_cpu_pct")
    spans = [
        x("ingest.fetch", 11.0, 0.01, records=5, batch=0, cpu_s=0.005),
        x("transform.dispatch", 12.0, 0.03, records=5, batch=0,
          cpu_s=0.003),
        x("transform.launch", 12.0, 0.02, records=5, batch=0,
          cpu_s=0.002),                                # nested: not read
        x("load.commit", 13.0, 0.06, records=5, batch=0, cpu_s=0.002),
        x("load.commit", 5.0, 1.0, records=5, batch=1, cpu_s=1.0),
    ]
    # 10 ms on the CPU over 100 ms of stage spans
    assert read(run_of(spans)) == pytest.approx(10.0)
    assert read(run_of(spans[2:3])) is None
    # a program whose spans carry no cpu_s has nothing to read
    assert read(run_of([x("load.commit", 13.0, 0.06, records=5)])) is None
