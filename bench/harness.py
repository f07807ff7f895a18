"""What every cell's run shares: finding a cell's files by name, the chip
check, the compile cache, the traced window, the per-layer metric readers,
and the result line.

A cell is an entry of ``workloads`` in ``BENCHMARK.json``. Its
configuration lives in ``configs/<config>.json`` (which names the
deployment module that drives it), its traffic in ``traffic/<traffic>.json``,
each per-layer metric's reader in ``metrics/<metric>.py``, the limit of
each number that decides ``correct`` in ``limits/<number>.json`` and the
chips' peaks in ``peaks.json``. Adding a cell, a configuration, a mix or a
metric adds files; no file here changes.
"""
from __future__ import annotations

import contextlib
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import threading
import time
from pathlib import Path
from typing import Callable, Dict, List, Optional

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


class BenchError(RuntimeError):
    """The run cannot be measured (no chip, a missing file, a sizing that
    breaks the configuration): exit non-zero with no result."""


def read_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path, name: str):
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def load_cell(workload: str) -> dict:
    """The cell's entry, configuration, traffic and the benchmark file."""
    bench = read_json(ROOT / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise BenchError(f"no workload {workload!r} in BENCHMARK.json")
    cell = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    config = read_json(ROOT / configs[cell["config"]]["file"])
    traffic = read_json(BENCH / "traffic" / f"{cell['traffic']}.json")
    return {"bench": bench, "cell": cell, "config": config,
            "traffic": traffic}


def metrics_of(bench: dict, workload: str, kind: str) -> List[dict]:
    """The cell's end-to-end or per-layer metrics."""
    return [m for m in bench[kind]
            if "workloads" not in m or workload in m["workloads"]]


def limit_of(name: str, config: dict) -> float:
    """The limit of one compared number: the configuration's own, else
    ``limits/<name>.json``."""
    own = config.get("limits", {})
    if name in own:
        return float(own[name])
    path = BENCH / "limits" / f"{name}.json"
    if not path.exists():
        raise BenchError(f"no limit for {name!r} ({path})")
    return float(read_json(path)["limit"])


def peaks_of(kind: str) -> dict:
    table = read_json(BENCH / "peaks.json")["devices"]
    if kind not in table:
        raise BenchError(f"no peaks for device kind {kind!r} in peaks.json")
    return table[kind]


# ------------------------------------------------------------------ device
def require_chips(n: int):
    """The accelerator devices, or ``BenchError`` when JAX's platform is not
    a TPU or has fewer than ``n`` chips."""
    import jax
    platform = jax.default_backend()
    if platform != "tpu":
        raise BenchError(f"JAX's platform is {platform!r}, not a TPU")
    devices = jax.devices()
    if len(devices) < n:
        raise BenchError(f"{n} chips wanted, {len(devices)} found")
    return devices


def enable_cache() -> str:
    """The persistent compile cache at the checkout's fixed ``.jax_cache``
    (or ``$JAX_COMPILATION_CACHE_DIR``), holding every compile however
    short, so that only a checkout's first run compiles."""
    import jax
    from repro.core.device import enable_compile_cache
    path = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileLog:
    """When this process obtained XLA programs (compiled, or loaded from
    the persistent cache) and when it compiled one afresh (a cache miss),
    to show that nothing is obtained inside the measured window and that
    only a checkout's first run compiles."""

    OBTAINED = "/jax/core/compile/backend_compile_duration"
    MISS = "/jax/compilation_cache/cache_misses"

    def __init__(self):
        import jax
        self.obtained: List[float] = []
        self.misses: List[float] = []
        self._lock = threading.Lock()
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event: str, duration: float, **_) -> None:
        if event == self.OBTAINED:
            with self._lock:
                self.obtained.append(time.perf_counter())

    def _on_event(self, event: str, **_) -> None:
        if event == self.MISS:
            with self._lock:
                self.misses.append(time.perf_counter())

    def count(self, which: str, lo: float = float("-inf"),
              hi: float = float("inf")) -> int:
        with self._lock:
            return sum(lo <= t < hi for t in getattr(self, which))


def device_info(devices, used: int) -> dict:
    peak = 0
    for d in devices[:used]:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": used, "memory_peak_bytes": peak}


# ------------------------------------------------------------------- trace
class Profiler:
    """The profiler trace of one window, kept in a temporary directory
    that is removed once it has been read. Host annotations mark the
    window; device events come from the chip's own planes."""

    def __init__(self, annotations):
        import jax
        self.jax = jax
        self.dir = tempfile.mkdtemp(prefix="bench-trace-")
        self.annotations = tuple(annotations)

    def start(self) -> None:
        opts = self.jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        opts.enable_hlo_proto = False
        self.jax.profiler.start_trace(self.dir, profiler_options=opts)

    def window(self):
        return self.jax.profiler.TraceAnnotation("bench.window")

    def stop_and_reduce(self) -> Optional[dict]:
        from bench import tracefile
        self.jax.profiler.stop_trace()
        try:
            paths = sorted(Path(self.dir).rglob("*.xplane.pb"))
            if not paths:
                return None
            return tracefile.reduce(tracefile.load(
                str(paths[-1]), self.annotations + ("bench.window",)))
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def annotated_tracer():
    """A ``StageTracer`` whose spans are also profiler annotations, so the
    stage seams of the program appear on the device trace's clock."""
    import jax
    from repro.observability.tracer import StageTracer, _Span

    class _AnnotatedSpan(_Span):
        __slots__ = ("_ann",)

        def __enter__(self):
            self._ann = jax.profiler.TraceAnnotation(self.name)
            self._ann.__enter__()
            return super().__enter__()

        def __exit__(self, *exc):
            out = super().__exit__(*exc)
            self._ann.__exit__(*exc)
            return out

    class AnnotatedTracer(StageTracer):
        def span(self, name, lane=None):
            return _AnnotatedSpan(self, name, lane)

    return AnnotatedTracer()


# ------------------------------------------------------------------ output
def read_per_layer(bench: dict, workload: str, run) -> Dict[str, dict]:
    """Each per-layer metric of the cell from its reader; a reader that
    finds nothing to read returns None and the metric is left out."""
    out = {}
    for m in metrics_of(bench, workload, "per_layer"):
        mod = load_module(BENCH / "metrics" / f"{m['name']}.py",
                          f"bench_metric_{m['name'].replace('.', '_')}")
        value = mod.read(run)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def result_line(correct: bool, attempted: int, failed: int, metrics: dict,
                device: dict, compared: Dict[str, dict],
                breakdown: Optional[dict] = None) -> str:
    res = {"correct": bool(correct), "attempted": int(attempted),
           "failed": int(failed), "metrics": metrics, "device": device}
    if breakdown is not None:
        res["breakdown"] = breakdown
    res["compared"] = compared
    return json.dumps(res)


def print_compared(compared: Dict[str, dict], stream=None) -> None:
    stream = stream or sys.stderr
    for name, c in compared.items():
        ok = "ok" if c["value"] <= c["limit"] else "FAILED"
        print(f"compared {name} {c['value']!r} limit {c['limit']!r} {ok}",
              file=stream)
    stream.flush()


@contextlib.contextmanager
def quiet_stdout():
    """Route anything the program prints to standard error, so the result
    stays the last line of standard output."""
    saved = sys.stdout
    sys.stdout = sys.stderr
    try:
        yield
    finally:
        sys.stdout = saved


def sleep_until(t: float, check: Optional[Callable[[], None]] = None) -> None:
    while True:
        left = t - time.perf_counter()
        if left <= 0:
            return
        if check is not None:
            check()
        time.sleep(min(left, 0.05))


def prepare_process() -> None:
    """Put the program and the benchmark on the import path, and keep the
    TPU runtime from writing its logs to a fixed path outside the
    checkout."""
    src = str(ROOT / "src")
    if src not in sys.path:
        sys.path.insert(0, src)
    if str(ROOT) not in sys.path:
        sys.path.insert(0, str(ROOT))
    os.environ.setdefault("TPU_LOG_DIR", "disabled")


def span_totals(run, name: str, key: str):
    """(seconds, summed ``key`` argument, span count) of the stage spans
    called ``name`` that started inside the run's window."""
    lo, hi = run.window
    secs = total = n = 0
    for ph, nm, _lane, t0, dur, args in run.spans:
        if nm == name and ph == "X" and lo <= t0 < hi:
            secs += dur
            total += (args or {}).get(key, 0)
            n += 1
    return secs, total, n


def ms_per_k(run, name: str, key: str):
    """Span milliseconds per 1,000 of the spans' ``key`` argument."""
    secs, total, _ = span_totals(run, name, key)
    return secs * 1e3 / (total / 1e3) if total else None


def idle_pct(run):
    dt = run.device_trace
    if not dt or dt["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - dt["busy_s"] / dt["window_s"])
