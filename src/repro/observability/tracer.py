"""Stage-span tracer: per-batch spans over the pipeline's stage seams,
exportable as Chrome-trace/Perfetto JSON and, while a ``jax.profiler``
trace runs, written into it as host annotations.

The enable/disable seam copies ``durability.faults``'s ``NULL_INJECTOR``
pattern exactly: every instrumented component holds a ``tracer``
attribute defaulting to the module singleton ``NULL_TRACER``, whose
``span()`` returns one shared, stateless no-op context manager and whose
``record()`` does nothing — the disabled hot path costs two attribute
lookups and a call, allocates NOTHING persistent, and needs no
``if tracing:`` branches at the call sites. Swap in a ``StageTracer``
and the same call sites emit real spans.

Span seams and their arguments. Every span made by ``span()`` also
carries ``cpu_s``, the thread CPU time over the span (wall time minus
``cpu_s`` is time the thread waited: a lock, the interpreter lock, the
device, a queue). ``batch`` is the worker's fetch ordinal, so
``(worker, batch)`` follows one fetched batch from stage to stage;
the retry path, whose records come from many fetches, carries -1.
Indented seams nest inside the one above them on the same thread.

    ingest.pump           master pumps under the cache lock   rows
    ingest.fetch          broker poll -> hand-off             records, batch
    transform.queue_wait  hand-off stamp -> transform's get   records, batch
    transform.dispatch    one batch's device transform        records, batch
      transform.snapshot  cache lock + both cache snapshots   records, batch,
                                                              upload_bytes
      transform.launch    pack, H2D, jit call, async D2H      records, batch
    load.queue_wait       hand-off stamp -> load's get        records, batch
    load.commit           warehouse load + offset commit      records, batch
      load.to_host        the step's one blocking D2H sync    records, batch
      load.warehouse      partition sort, warehouse lock,     records, batch
                          commit, serving delta publish
    load.retry            late records' transform + load      records, batch
                          (load.to_host, load.warehouse nest here)
    serving.fold          materialized-view delta fold        deltas, rows, epoch
    query.batch           batched report plan execute         queries, epoch
    checkpoint.step       durability journal append
    repartition.*         plan / reroute / migrate phases
    control.decide        one autonomous policy decision

``records`` on a nested seam is the count of its parent (records
fetched; records loaded under ``load.commit``); on ``load.retry``, the
late records popped. The two ``*.queue_wait`` spans are recorded with
explicit times (``record()``): they start on the producing thread, so
they are not profiler annotations and carry no ``cpu_s``.

Lanes: a span lands in the lane (Chrome ``tid``) named after its thread
(worker stage threads are named ``w0.ingest`` etc.), so the Perfetto
view shows one swimlane per worker stage. Export with
``tracer.export_chrome_trace(path)`` and open the file at
https://ui.perfetto.dev — see docs/OBSERVABILITY.md for a worked run.
"""
from __future__ import annotations

import json
import threading
import time
from typing import Dict, List, Optional


class _NullSpan:
    """The shared no-op span. Stateless (``__slots__ = ()``): entering,
    exiting, annotating and dropping it all do nothing, so ONE instance
    serves every disabled call site forever — zero allocation."""

    __slots__ = ()

    def __enter__(self) -> "_NullSpan":
        return self

    def __exit__(self, *exc) -> bool:
        return False

    def put(self, key, value) -> None:
        pass

    def drop(self) -> None:
        pass


_NULL_SPAN = _NullSpan()


class _NullTracer:
    """Disabled tracer: ``span()``/``instant()``/``record()`` are
    allocation-free no-ops (pinned by a tracemalloc test). Default for
    every component's ``tracer`` attribute — the same seam as
    ``NULL_INJECTOR``."""

    __slots__ = ()
    enabled = False

    def span(self, name: str, lane: Optional[str] = None) -> _NullSpan:
        return _NULL_SPAN

    def instant(self, name: str, lane: Optional[str] = None) -> None:
        return None

    def record(self, name: str, t_start: float, t_end: float,
               **args) -> None:
        return None

    def count(self, name: str, value: int,
              lane: Optional[str] = None) -> None:
        return None


NULL_TRACER = _NullTracer()


class _Span(object):
    """One live span: context manager capturing the wall interval, the
    thread CPU time over it (``cpu_s``, when the tracer has a CPU clock)
    and optional args; appended to the tracer's event list (under its
    lock) on exit. ``drop()`` cancels recording — used to skip empty
    broker polls so idle traces stay readable."""

    __slots__ = ("_tracer", "name", "lane", "_t0", "_c0", "_args",
                 "_dropped")

    def __init__(self, tracer: "StageTracer", name: str,
                 lane: Optional[str]):
        self._tracer = tracer
        self.name = name
        self.lane = lane
        self._t0 = 0.0
        self._c0 = 0.0
        self._args: Optional[Dict[str, object]] = None
        self._dropped = False

    def put(self, key: str, value) -> None:
        """Attach one argument (shown in the Perfetto detail pane)."""
        if self._args is None:
            self._args = {}
        self._args[key] = value

    def drop(self) -> None:
        self._dropped = True

    def __enter__(self) -> "_Span":
        tr = self._tracer
        self._t0 = tr._clock()
        if tr._cpu_clock is not None:
            self._c0 = tr._cpu_clock()
        return self

    def __exit__(self, *exc) -> bool:
        if not self._dropped:
            tr = self._tracer
            # CPU interval read inside the wall interval: cpu_s <= dur
            if tr._cpu_clock is not None:
                self.put("cpu_s", tr._cpu_clock() - self._c0)
            t1 = tr._clock()
            tr._record(self.name,
                       self.lane or threading.current_thread().name,
                       self._t0, t1 - self._t0, self._args)
        return False


class _AnnotatedSpan(_Span):
    """The span ``StageTracer.span()`` returns: a ``_Span`` that is also a
    ``jax.profiler.TraceAnnotation`` over its interval, so a profiler
    trace of the running system shows the stage seams on the device
    trace's clock. The base ``_Span`` stays unannotated, so a subclass
    that annotates by itself shows each span once, not twice."""

    __slots__ = ("_ann",)

    def __enter__(self) -> "_AnnotatedSpan":
        self._ann = self._tracer._annotation(self.name)
        self._ann.__enter__()
        return super().__enter__()

    def __exit__(self, *exc) -> bool:
        super().__exit__(*exc)
        self._ann.__exit__(*exc)
        return False


class StageTracer:
    """Collects spans from every pipeline thread; lock guards only the
    event-list append (the measured interval is computed outside it).
    ``clock`` times the spans (``perf_counter``, the profiler's host
    clock); ``cpu_clock`` gives each span its ``cpu_s`` (None: no CPU
    time). Export with ``to_chrome()`` / ``export_chrome_trace()``."""

    enabled = True

    def __init__(self, clock=time.perf_counter, max_events: int = 1 << 20,
                 cpu_clock=time.thread_time):
        # imported here: importing the tracer does not import JAX
        from jax.profiler import TraceAnnotation
        self._annotation = TraceAnnotation
        self._clock = clock
        self._cpu_clock = cpu_clock
        self._t0 = clock()
        self._lock = threading.Lock()
        self._events: List[tuple] = []   # (ph, name, lane, t_start, dur, args)
        self.max_events = max_events
        self.dropped_events = 0

    # ------------------------------------------------------------ write side
    def span(self, name: str, lane: Optional[str] = None) -> _Span:
        return _AnnotatedSpan(self, name, lane)

    def record(self, name: str, t_start: float, t_end: float,
               **args) -> None:
        """A span whose times the caller took on this tracer's clock, in
        the calling thread's lane: for an interval that began on another
        thread (a hand-off wait), which no context manager can enclose."""
        self._record(name, threading.current_thread().name, t_start,
                     t_end - t_start, args or None)

    def instant(self, name: str, lane: Optional[str] = None) -> None:
        self._record(name, lane or threading.current_thread().name,
                     self._clock(), None, None, ph="i")

    def count(self, name: str, value: int,
              lane: Optional[str] = None) -> None:
        """A counter increment: an event of phase "C" whose ``value``
        argument is the amount counted now (a metric sums the events that
        fall in its window)."""
        self._record(name, lane or threading.current_thread().name,
                     self._clock(), None, {"value": value}, ph="C")

    def _record(self, name, lane, t_start, dur, args, ph="X") -> None:
        with self._lock:
            if len(self._events) >= self.max_events:
                self.dropped_events += 1
                return
            self._events.append((ph, name, lane, t_start, dur, args))

    # ------------------------------------------------------------- read side
    def events(self) -> List[tuple]:
        with self._lock:
            return list(self._events)

    def span_names(self) -> List[str]:
        names: List[str] = []
        for ev in self.events():
            if ev[1] not in names:
                names.append(ev[1])
        return names

    def clear(self) -> None:
        with self._lock:
            self._events = []
            self.dropped_events = 0

    def to_chrome(self) -> Dict[str, object]:
        """Chrome-trace JSON object (Perfetto/chrome://tracing loadable):
        complete ("X") events with microsecond timestamps relative to
        tracer start, one ``tid`` per lane plus ``thread_name`` metadata
        so lanes are labeled swimlanes."""
        events = self.events()
        lanes: Dict[str, int] = {}
        out: List[Dict[str, object]] = [
            {"name": "process_name", "ph": "M", "pid": 1, "tid": 0,
             "args": {"name": "dod-etl"}}]
        for ev in events:
            lane = ev[2]
            if lane not in lanes:
                lanes[lane] = len(lanes) + 1
                out.append({"name": "thread_name", "ph": "M", "pid": 1,
                            "tid": lanes[lane], "args": {"name": lane}})
        for ph, name, lane, t_start, dur, args in events:
            rec: Dict[str, object] = {
                "name": name, "cat": name.split(".", 1)[0], "ph": ph,
                "ts": round((t_start - self._t0) * 1e6, 3),
                "pid": 1, "tid": lanes[lane]}
            if ph == "X":
                rec["dur"] = round((dur or 0.0) * 1e6, 3)
            if args:
                rec["args"] = args
            elif ph == "i":
                rec["s"] = "t"
            out.append(rec)
        return {"traceEvents": out, "displayTimeUnit": "ms",
                "otherData": {"dropped_events": self.dropped_events}}

    def export_chrome_trace(self, path: str) -> str:
        with open(path, "w") as f:
            json.dump(self.to_chrome(), f)
        return path


__all__ = ["NULL_TRACER", "StageTracer"]
