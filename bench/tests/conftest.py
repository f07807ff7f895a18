"""Shared fixtures of the harness's own tests, run on the CPU:

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests
"""
import sys
import time
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)


@pytest.fixture
def copied_cache_uploads(monkeypatch):
    """XLA:CPU may alias a suitably aligned numpy array instead of copying
    it, so on the CPU a cache's device mirror can change under an in-flight
    transform (a TPU copies host to device). Upload copies here, so that a
    sound run on the CPU is what it is on the chip."""
    import jax.numpy as jnp
    from repro.core import cache

    def device_state(self):
        if self._device is None or self._dirty:
            self._device = tuple(jnp.asarray(np.array(a, copy=True))
                                 for a in (self.keys, self.values, self.txn))
            self._dirty.clear()
        return self._device

    monkeypatch.setattr(cache.InMemoryTable, "device_state", device_state)


SMALL = {  # traffic small enough for the CPU
    "stream": {"rate_records_s": 300, "warmup_s": 1.0},
    "backlog": {"fill_records_s": 20000, "warmup_s": 1.0},
}


def run_small(config, traffic, seed=987654321012, seconds=1.5, **kw):
    """One run of a configuration under a traffic mix on the CPU at a
    small size: the harness's run without its look for a chip. Mixes that
    no cell of ``BENCHMARK.json`` uses yet are covered too. Returns (run,
    correct)."""
    from bench import harness, steelworks
    spec = {"config": harness.read_json(
                harness.BENCH / "configs" / f"{config}.json"),
            "traffic": harness.read_json(
                harness.BENCH / "traffic" / f"{traffic}.json")}
    over = dict(SMALL[spec["traffic"]["mode"]])
    run = steelworks.run(spec, seed, seconds, False, time.perf_counter(),
                         overrides=over, **kw)
    correct = all(v <= harness.limit_of(k, spec["config"])
                  for k, v in run.compared.items())
    return run, correct
