"""Reduction of a JAX profiler trace to device busy time, program time and
idle gaps.

``load`` turns an ``.xplane.pb`` into plain data: for every TPU plane its
op and module lines with (name, start_ns, duration_ns) events, and the host annotations
(the stage spans the benchmark mirrors into the trace). ``reduce`` works on
that plain form only, so it can be checked on a small recorded trace
without a chip.

Busy time is the union of the intervals in which an operation ran on a
device, clipped to the window and averaged over the devices. A program's
device time is the sum of its executions on the devices' module line.
"""
from __future__ import annotations

import re
from typing import Dict, List, Optional, Tuple

import numpy as np

DEVICE_PLANE = "/device:TPU:"   # a TPU host also has a "/device:CUSTOM:" plane
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW_ANNOTATION = "bench.window"


def load(path: str, annotations: Tuple[str, ...]) -> Dict:
    """Plain form of one trace: device planes' lines and the host events
    whose name is in ``annotations``."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices: Dict[str, Dict[str, list]] = {}
    host: List[list] = []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PLANE):
            devices[plane.name] = {
                line.name: [[ev.name, float(ev.start_ns),
                             float(ev.duration_ns)] for ev in line.events]
                for line in plane.lines
                if line.name in (OPS_LINE, MODULES_LINE)}
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in annotations:
                        host.append([ev.name, float(ev.start_ns),
                                     float(ev.duration_ns)])
    return {"devices": devices, "host": host}


def _union(iv: np.ndarray) -> np.ndarray:
    """Merge [start, end] rows into disjoint sorted intervals."""
    if not len(iv):
        return iv.reshape(0, 2)
    iv = iv[np.argsort(iv[:, 0], kind="stable")]
    starts, ends = iv[:, 0], np.maximum.accumulate(iv[:, 1])
    new = np.ones(len(iv), bool)
    new[1:] = starts[1:] > ends[:-1]
    idx = np.flatnonzero(new)
    return np.stack([starts[idx], np.append(ends[idx[1:] - 1], ends[-1])],
                    axis=1)


def _clip(iv: np.ndarray, lo: float, hi: float) -> np.ndarray:
    iv = np.clip(iv, lo, hi)
    return iv[iv[:, 1] > iv[:, 0]]


def window_of(trace: Dict) -> Optional[Tuple[float, float]]:
    """(start_ns, end_ns) of the window annotation, if the trace has it."""
    for name, start, dur in trace["host"]:
        if name == WINDOW_ANNOTATION:
            return start, start + dur
    return None


def _module_name(name: str) -> str:
    return re.sub(r"\(\d+\)$", "", name)


def reduce(trace: Dict, top: int = 10) -> Optional[Dict]:
    """Busy and window seconds, per-program device seconds and calls, the
    longest-running programs and the longest idle gaps, all inside the
    window annotation. None when the trace has no window or no device
    plane."""
    win = window_of(trace)
    if win is None or not trace["devices"]:
        return None
    lo, hi = win
    busy_ns, gaps = [], []
    modules: Dict[str, float] = {}
    calls: Dict[str, int] = {}
    for lines in trace["devices"].values():
        iv = np.array([[s, s + d] for _, s, d in lines.get(OPS_LINE, [])],
                      np.float64)
        iv = _clip(_union(iv.reshape(-1, 2)), lo, hi)
        busy_ns.append(float((iv[:, 1] - iv[:, 0]).sum()))
        edges = np.concatenate([[lo], iv.ravel(), [hi]]).reshape(-1, 2)
        gaps += [(float(a), float(b)) for a, b in edges if b > a]
        for name, s, d in lines.get(MODULES_LINE, []):
            a, b = max(s, lo), min(s + d, hi)
            if b > a:
                m = _module_name(name)
                modules[m] = modules.get(m, 0.0) + (b - a) * 1e-9
                calls[m] = calls.get(m, 0) + 1
    gaps.sort(key=lambda g: g[0] - g[1])
    return {
        "busy_s": float(np.mean(busy_ns)) * 1e-9,
        "window_s": (hi - lo) * 1e-9,
        "modules": modules,
        "calls": calls,
        "device_ops": sorted(modules.items(), key=lambda kv: -kv[1])[:top],
        "idle_gaps": [[_gap_name(trace["host"], a, b), (b - a) * 1e-9]
                      for a, b in gaps[:top]],
    }


def _gap_name(host: List[list], a: float, b: float) -> str:
    """The host annotation that overlaps an idle gap the most."""
    best, name = 0.0, "no stage span"
    for n, s, d in host:
        if n == WINDOW_ANNOTATION:
            continue
        ov = min(s + d, b) - max(s, a)
        if ov > best:
            best, name = ov, n
    return name
