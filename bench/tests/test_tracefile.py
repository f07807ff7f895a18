"""The trace reduction on small traces: a hand-made one whose answers are
worked out by hand, and an excerpt recorded on a TPU v5e (the first 30 ms
of a traced oee.backlog window, as ``tracefile.load`` reads it)."""
import json
from pathlib import Path

import pytest

from bench import tracefile

DATA = Path(__file__).resolve().parent / "data"


def test_hand_made_trace():
    trace = {
        "devices": {"/device:TPU:0": {
            "XLA Ops": [["fusion.1", 0, 10], ["fusion.2", 5, 15],
                        ["fusion.3", 30, 10], ["fusion.4", 150, 10]],
            "XLA Modules": [["jit_f(12)", 0, 20], ["jit_g(3)", 30, 10],
                            ["jit_g(3)", 150, 10]]}},
        "host": [["bench.window", 0, 100], ["load.commit", 45, 45],
                 ["ingest.fetch", 20, 5]]}
    r = tracefile.reduce(trace)
    assert r["busy_s"] == pytest.approx(30e-9)
    assert r["window_s"] == pytest.approx(100e-9)
    assert r["modules"] == pytest.approx({"jit_f": 20e-9, "jit_g": 10e-9})
    assert r["calls"] == {"jit_f": 1, "jit_g": 1}
    assert [g[0] for g in r["idle_gaps"]] == ["load.commit", "ingest.fetch"]
    assert r["idle_gaps"][0][1] == pytest.approx(60e-9)
    assert r["device_ops"][0][0] == "jit_f"


def test_no_window_no_reading():
    assert tracefile.reduce({"devices": {"/device:TPU:0": {}},
                             "host": []}) is None


def test_union():
    import numpy as np
    iv = np.array([[5, 7], [0, 3], [2, 4], [6, 9], [10, 11]], float)
    assert tracefile._union(iv).tolist() == [[0, 4], [5, 9], [10, 11]]


def test_recorded_v5e_excerpt():
    """The first 3 ms of a traced oee.backlog window on a TPU v5e: busy
    time by the reducer equals a plain sweep over the op intervals."""
    trace = json.loads((DATA / "trace_v5e_excerpt.json").read_text())
    r = tracefile.reduce(trace)
    lo, hi = tracefile.window_of(trace)
    ops = sorted((max(s, lo), min(s + d, hi))
                 for _, s, d in trace["devices"]["/device:TPU:0"]["XLA Ops"])
    busy, end = 0.0, lo
    for a, b in ops:
        if b > end:
            busy += b - max(a, end)
            end = b
    assert r["busy_s"] == pytest.approx(busy * 1e-9)
    assert 0 < r["busy_s"] < r["window_s"] == pytest.approx(3e-3)
    assert "jit_transform_rollup_kernel" in r["modules"]
