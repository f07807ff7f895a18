"""The tpcdi.trade-backlog cell on the CPU at a small size: a sound run is
correct, the float32-lanes control and a broken timed path are not, and a
traced run's line carries the cell's per-layer metrics that a CPU trace
can give (the device's roofline needs the chip)."""
import json
import time

import numpy as np
import pytest

from bench import harness, run, tpcdi

CELL = "tpcdi.trade-backlog"
SMALL = {"fill_records_s": 60000, "warmup_s": 0.5,
         "config": {"n_accounts": 20000, "n_securities": 2000,
                    "n_customers": 50000}}


def run_small(seed=3221225901, **kw):
    spec = harness.load_cell(CELL)
    r = tpcdi.run(spec, seed, 1.5, False, time.perf_counter(),
                  overrides=json.loads(json.dumps(SMALL)), **kw)
    correct = all(v <= harness.limit_of(k, spec["config"])
                  for k, v in r.compared.items())
    return r, correct


def test_sound_run_is_correct(copied_cache_uploads):
    r, correct = run_small()
    assert correct, r.compared
    assert r.notes["backlog_left"] > 0


def test_float32_control_is_not_correct(copied_cache_uploads):
    r, correct = run_small(control=True)
    assert not correct
    assert r.compared["dimtrade_rows_off"] > 0
    assert r.notes["compared_sound"]["dimtrade_rows_off"] == 0


def alter_row(pipe):
    inner = pipe.warehouse.upsert

    def altered(rows):
        rows = np.array(rows)
        rows[:1, 1] += 1
        return inner(rows)
    pipe.warehouse.upsert = altered


def drop_half(pipe):
    inner = pipe.warehouse.upsert
    pipe.warehouse.upsert = lambda rows: inner(rows[: (len(rows) + 1) // 2])


def apply_twice(pipe):
    inner = pipe.warehouse.upsert

    def twice(rows):
        inner(rows)
        return inner(rows)
    pipe.warehouse.upsert = twice


@pytest.mark.parametrize("fault", [alter_row, drop_half, apply_twice],
                         ids=lambda f: f.__name__)
def test_fault_is_not_correct(fault, copied_cache_uploads):
    r, correct = run_small(fault=fault)
    assert not correct, r.compared


@pytest.fixture
def newest_version(monkeypatch):
    """The point-in-time probe without its bound: every trade joins its
    account's newest cached version, whatever its T_DTS."""
    import jax
    import jax.numpy as jnp
    from repro.core import cache
    from repro.core.dimtrade import dimtrade_transform_kernel
    inner = cache.lookup_asof

    def newest(keys, times, *a, **k):
        return inner(keys, jnp.full_like(times, np.iinfo(np.int32).max),
                     *a, **k)
    monkeypatch.setattr(cache, "lookup_asof", newest)
    dimtrade_transform_kernel.clear_cache()
    yield
    monkeypatch.undo()
    dimtrade_transform_kernel.clear_cache()


def test_newest_version_is_not_correct(newest_version, copied_cache_uploads):
    r, correct = run_small()
    assert not correct, r.compared
    assert r.compared["dimtrade_rows_off"] > 0


def test_traced_line(copied_cache_uploads):
    line = run.run_cell(CELL, 3221225903, 1.5, True, require_tpu=False,
                        t_process=time.perf_counter(),
                        overrides=json.loads(json.dumps(SMALL)))
    res = json.loads(line)
    assert res["correct"], res["compared"]
    assert set(res["compared"]) == {"bad_records", "dimtrade_rows_off",
                                    "caches_grown"}
    m = res["metrics"]
    assert m["load_upsert_ms_per_krec"]["value"] > 0
    assert m["trades_held_pct"]["value"] > 0
    assert "dimtrade_kernel_roofline" not in m      # no device trace here


def test_roofline_bytes():
    from bench import tpcdi_roofline as rl
    assert rl.dimtrade_bytes(1) == 16 * 4 + 2 * 8 + 32 + 4 + 32 + 21 * 4 + 1
    assert rl.dimtrade_bytes(0) == 0
