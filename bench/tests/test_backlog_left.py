"""A backlog run reads what is left of its backlog at the window's close,
and stops loading there, in a traced run as in an untraced one. In a
traced run the profiler's trace is stopped and reduced after the window;
here a stand-in profiler holds that reduction for a while, in which a
cluster that kept loading would load far more than is in flight, or the
whole small backlog."""
import contextlib
import time

import numpy as np
import pytest

HOLD_S = 5.0

# (fill records/s, warm-up s, window s): 675,000 records, over 3x what the
# CPU drains in 1.5 s; 5,000 records, a small share of what it drains in
# 2.5 s
CASES = {"lasts_the_window": (450000, 0.5, 1.0),
         "empties_in_the_window": (2000, 1.0, 1.5)}


class HeldProfiler:
    """Stands in for ``harness.Profiler``: records no trace, and its
    ``stop_and_reduce`` returns once ``HOLD_S`` have passed or the cluster
    has loaded every record of the backlog."""

    def __init__(self, held):
        self.held = held

    def start(self):
        pass

    def window(self):
        return contextlib.nullcontext()

    def stop_and_reduce(self):
        pipe, n = self.held["pipe"], self.held["records"]
        deadline = time.perf_counter() + HOLD_S
        while (pipe.warehouse.rows_loaded < n
               and time.perf_counter() < deadline):
            time.sleep(0.01)
        return None


@pytest.mark.parametrize("case", sorted(CASES))
def test_backlog_left_read_at_window_close(case, copied_cache_uploads,
                                           monkeypatch):
    import json
    from bench import harness, run, steelworks
    fill, warmup, seconds = CASES[case]
    held = {"records": int(np.ceil(fill * (warmup + seconds)))}
    monkeypatch.setattr(harness, "Profiler",
                        lambda annotations: HeldProfiler(held))
    inner = steelworks.run

    def kept(*a, **k):
        held["run"] = inner(*a, **k)
        return held["run"]
    monkeypatch.setattr(steelworks, "run", kept)

    def call():
        return run.run_cell(
            "oee.backlog", 987654321014, seconds, True, require_tpu=False,
            t_process=time.perf_counter(),
            fault=lambda pipe, engine, front: held.update(pipe=pipe),
            overrides={"fill_records_s": fill, "warmup_s": warmup})

    if case == "empties_in_the_window":
        with pytest.raises(harness.BenchError, match="backlog emptied"):
            call()
        assert held["run"].notes["backlog_left"] == 0
        return
    res = json.loads(call())
    assert res["correct"], res["compared"]
    notes, pipe = held["run"].notes, held["pipe"]
    in_flight = len(pipe.workers) * pipe.cfg.buffer_capacity
    loaded_close = held["records"] - notes["backlog_close"]
    assert notes["backlog_left"] > 0
    assert abs(notes["backlog_left"] - notes["backlog_close"]) <= in_flight
    assert pipe.warehouse.rows_loaded - loaded_close <= in_flight
