"""A run with the timed path broken underneath comes out not correct, once
for each fault a cell can have; a sound run and the bfloat16 control are
the two ends. The harness's look for a chip is skipped (these run on the
CPU, at a small size). A single chip has no exchange between chips, so that
fault has no case here."""
import numpy as np
import pytest

from conftest import run_small

# (configuration, traffic): the cells, and the mixes kept for later cells
CELLS = [("steelworks-oee", "steady"),
         ("steelworks-oee", "backlog"), ("steelworks-oee", "dashboard")]
IDS = [f"{c}.{t}" for c, t in CELLS]


def fold_keeps_state(monkeypatch):
    """A view fold that returns its state unchanged."""
    def fault(pipe, engine, front):
        from repro.serving import engine as engine_mod
        monkeypatch.setattr(engine_mod, "combine_fold",
                            lambda state, delta: state)
    return fault


def load_drops_half(monkeypatch):
    """Half of each loaded block left out."""
    def fault(pipe, engine, front):
        wh = pipe.warehouse
        inner = wh.load_partitioned

        def half(facts, *a, **k):
            return inner(facts[: (len(facts) + 1) // 2], *a, **k)
        monkeypatch.setattr(wh, "load_partitioned", half)
    return fault


def transform_alters_fact(monkeypatch):
    """One KPI of each dispatched block altered where it is produced."""
    def fault(pipe, engine, front):
        be = pipe.backend
        inner = be.transform_block

        def altered(*a, **k):
            block = inner(*a, **k)
            block._facts = block._facts.at[0, 6].multiply(1.01)
            return block
        monkeypatch.setattr(be, "transform_block", altered)
    return fault


def gather_alters_answer(monkeypatch):
    """Every point answer's means altered where the gather produces them."""
    def fault(pipe, engine, front):
        be = engine.backend
        inner = be.batch_gather_stats

        def altered(table, idx):
            out = np.array(inner(table, idx))
            out[:, -4:] *= 1.01
            return out
        monkeypatch.setattr(be, "batch_gather_stats", altered)
    return fault


FAULTS = {"fold_keeps_state": fold_keeps_state,
          "load_drops_half": load_drops_half,
          "transform_alters_fact": transform_alters_fact}


@pytest.mark.parametrize("cell", CELLS, ids=IDS)
def test_sound_run_is_correct(cell, copied_cache_uploads):
    run, correct = run_small(*cell)
    assert correct, run.compared


@pytest.mark.parametrize("cell", CELLS, ids=IDS)
def test_control_is_not_correct(cell, copied_cache_uploads):
    run, correct = run_small(*cell, control=True)
    assert not correct, run.compared


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("cell", CELLS, ids=IDS)
def test_fault_is_not_correct(cell, fault, copied_cache_uploads,
                              monkeypatch):
    run, correct = run_small(*cell, fault=FAULTS[fault](monkeypatch))
    assert not correct, run.compared


def test_altered_answer_is_not_correct(copied_cache_uploads, monkeypatch):
    run, correct = run_small("steelworks-oee", "dashboard",
                             fault=gather_alters_answer(monkeypatch))
    assert not correct, run.compared
    assert run.compared["answer_err"] > 1e-3


@pytest.mark.parametrize("broken", [False, True], ids=["sound", "broken"])
def test_result_line(broken, copied_cache_uploads, monkeypatch):
    """The whole run of the first cell of ``BENCHMARK.json`` past the look
    for a chip: the result line's ``correct`` follows the comparison, with
    the compared numbers last, each beside its limit, and the metrics are
    the cell's end-to-end metrics."""
    import json
    import time
    from conftest import SMALL
    from bench import harness, run
    bench = harness.read_json(harness.ROOT / "BENCHMARK.json")
    cell = bench["workloads"][0]["name"]
    spec = harness.load_cell(cell)
    fault = load_drops_half(monkeypatch) if broken else None
    over = dict(SMALL[spec["traffic"]["mode"]])
    seconds = 1.5
    if spec["traffic"]["mode"] == "backlog":
        # run_cell refuses a backlog that empties: 675,000 records in a
        # 1.5 s run, over 3x what the CPU drains in it
        over.update(fill_records_s=450000, warmup_s=0.5)
        seconds = 1.0
    line = run.run_cell(cell, 987654321013, seconds, False,
                        require_tpu=False, t_process=time.perf_counter(),
                        fault=fault, overrides=over)
    res = json.loads(line)
    assert res["correct"] is (not broken)
    assert list(res)[-1] == "compared"
    assert set(res["compared"]["bad_records"]) == {"value", "limit"}
    assert set(res["metrics"]) == {
        m["name"] for m in harness.metrics_of(bench, cell, "end_to_end")}
