"""Plain reference of the steelworks deployments' semantics, in numpy only.

It imports nothing of the system under test and reads none of its tables:
it works from the records the benchmark generated and from the view
definitions in the configuration file.

* ``facts`` joins each production record with its equipment unit's status
  row and its quality inspection and computes the paper's OEE fact grain
  (arXiv:1907.06723 §4: availability x performance x quality, with the
  production window intersected with the status interval, Fig. 3).
* ``ViewFold`` aggregates facts into the report views (count, sum, min and
  max per segment and lane), one block at a time, so that the state after
  any prefix of the load order can be read.
* ``answer`` derives a dashboard query's answer from view tables.

Every function takes the ``dtype`` it computes in. The reference runs in
float64; the control runs the same code in bfloat16, the precision one
step below the float32 the configuration states.
"""
from __future__ import annotations

from typing import Dict, List, Tuple

import numpy as np

EPS = 1e-6
FACT_WIDTH = 10      # unit, t_start, t_end, A, P, Q, OEE, seg_on, seg_off, valid


def facts(prod: np.ndarray, status: np.ndarray, e_start: np.ndarray,
          e_end: np.ndarray, max_speed: np.ndarray, planned: np.ndarray,
          defects: np.ndarray, scrap: np.ndarray, dtype=np.float64
          ) -> np.ndarray:
    """Fact rows [n, 10] for production payloads ``prod`` [n, 8] (prod_id,
    unit, txn, t_start, t_end, qty, speed, order), joined with the status
    row of each record's unit (``status`` .. ``planned``, one value per
    record) and its quality row (``defects``, ``scrap``)."""
    c = lambda a: np.asarray(a).astype(dtype)
    t_start, t_end, qty = c(prod[:, 3]), c(prod[:, 4]), c(prod[:, 5])
    e_start, e_end = c(e_start), c(e_end)
    status, max_speed, planned = c(status), c(max_speed), c(planned)
    defects, scrap = c(defects), c(scrap)
    zero, one, eps = dtype(0), dtype(1), dtype(EPS)

    # Fig. 3: the part of the production window inside the status interval
    overlap = np.maximum(np.minimum(t_end, e_end)
                         - np.maximum(t_start, e_start), zero)
    duration = np.maximum(t_end - t_start, eps)
    seg_on = np.where(status > dtype(0.5), overlap, zero)
    seg_off = duration - seg_on
    # §4: the TPM indicators
    availability = np.clip(seg_on / np.maximum(planned, eps), zero, one)
    performance = np.clip(qty / np.maximum(max_speed * duration, eps),
                          zero, one)
    good = np.maximum(qty - defects - scrap, zero)
    quality = np.clip(good / np.maximum(qty, eps), zero, one)
    oee = availability * performance * quality
    out = np.empty((len(prod), FACT_WIDTH), dtype)
    out[:, 0] = c(prod[:, 1])
    out[:, 1] = t_start
    out[:, 2] = t_end
    out[:, 3] = availability
    out[:, 4] = performance
    out[:, 5] = quality
    out[:, 6] = oee
    out[:, 7] = seg_on
    out[:, 8] = seg_off
    out[:, 9] = one
    return out


# ------------------------------------------------------------------- views
def view_defs(n_units: int, views: Dict) -> List[Tuple[str, int, list]]:
    """(name, n_segments, lane columns) of the four steelworks report
    views, from the configuration's ``views`` block."""
    return [("oee_by_equipment", n_units, [3, 4, 5, 6]),
            ("kpi_by_unit_shift", n_units * views["n_shifts"], [3, 4, 5, 6]),
            ("downtime_by_equipment", n_units, [8, 7]),
            ("production_rate_windows", views["n_windows"], [7, 6])]


def segments(name: str, f: np.ndarray, n_units: int, views: Dict
             ) -> np.ndarray:
    unit = np.rint(f[:, 0].astype(np.float64)).astype(np.int64)
    t_start = f[:, 1].astype(np.float64)
    if name in ("oee_by_equipment", "downtime_by_equipment"):
        return unit
    if name == "kpi_by_unit_shift":
        shift = (np.floor(t_start / views["shift_len"]).astype(np.int64)
                 % views["n_shifts"])
        return unit * views["n_shifts"] + shift
    if name == "production_rate_windows":
        return (np.floor(t_start / views["window_len"]).astype(np.int64)
                % views["n_windows"])
    raise KeyError(name)


def empty_table(n_segments: int, n_lanes: int, dtype) -> np.ndarray:
    t = np.zeros((n_segments, 1 + 3 * n_lanes), dtype)
    t[:, 1 + n_lanes:1 + 2 * n_lanes] = np.inf
    t[:, 1 + 2 * n_lanes:] = -np.inf
    return t


class ViewFold:
    """Running view tables [S, 1 + 3L] (count | sums | mins | maxs), added
    to one block of facts at a time in ``dtype``."""

    def __init__(self, n_units: int, views: Dict, dtype=np.float64):
        self.n_units = n_units
        self.views = views
        self.dtype = dtype
        self.defs = view_defs(n_units, views)
        self.tables = {name: empty_table(s, len(lanes), dtype)
                       for name, s, lanes in self.defs}

    def add(self, f: np.ndarray) -> None:
        f = f[f[:, 9].astype(np.float64) > 0.5]
        if not len(f):
            return
        for name, n_seg, lanes in self.defs:
            seg = segments(name, f, self.n_units, self.views)
            keep = (seg >= 0) & (seg < n_seg)
            seg = seg[keep]
            vals = f[keep][:, lanes].astype(self.dtype)
            L = len(lanes)
            t = self.tables[name].copy()
            cnt = np.bincount(seg, minlength=n_seg).astype(self.dtype)
            t[:, 0] = t[:, 0] + cnt
            for j in range(L):
                sums = np.zeros(n_seg, self.dtype)
                np.add.at(sums, seg, vals[:, j])
                t[:, 1 + j] = t[:, 1 + j] + sums
                mins = np.full(n_seg, np.inf, self.dtype)
                np.minimum.at(mins, seg, vals[:, j])
                t[:, 1 + L + j] = np.minimum(t[:, 1 + L + j], mins)
                maxs = np.full(n_seg, -np.inf, self.dtype)
                np.maximum.at(maxs, seg, vals[:, j])
                t[:, 1 + 2 * L + j] = np.maximum(t[:, 1 + 2 * L + j], maxs)
            self.tables[name] = t

    def snapshot(self) -> Dict[str, np.ndarray]:
        return {k: v.copy() for k, v in self.tables.items()}


def kpi_rollup(f: np.ndarray, n_units: int, dtype=np.float64) -> np.ndarray:
    """Per-unit sums of the four KPIs and the fact count [n_units, 5]."""
    f = f[f[:, 9].astype(np.float64) > 0.5]
    unit = np.rint(f[:, 0].astype(np.float64)).astype(np.int64)
    out = np.zeros((n_units, 5), dtype)
    for j in range(4):
        np.add.at(out[:, j], unit, f[:, 3 + j].astype(dtype))
    out[:, 4] = np.bincount(unit, minlength=n_units).astype(dtype)
    return out


# ----------------------------------------------------------------- queries
def _split(t: np.ndarray):
    L = (t.shape[1] - 1) // 3
    return t[:, 0], t[:, 1:1 + L], t[:, 1 + L:1 + 2 * L], t[:, 1 + 2 * L:]


def _means(count, sums):
    with np.errstate(invalid="ignore", divide="ignore"):
        return np.where(count[:, None] > 0, sums / count[:, None], np.nan)


def answer(kind: str, arg: int, tables: Dict[str, np.ndarray]
           ) -> Dict[str, np.ndarray]:
    """A dashboard query's answer from view tables, as named arrays.
    ``arg`` is the unit (-1 for the fleet) of an ``oee`` query, the depth
    of a ``top_downtime`` query, and the view's index in ``VIEW_ARGS``
    for a ``view`` query."""
    if kind == "oee":
        cnt, sums, _, _ = _split(tables["oee_by_equipment"])
        if arg >= 0:
            c, s = cnt[arg], sums[arg]
        else:
            c, s = cnt.sum(), sums.sum(axis=0)
        with np.errstate(invalid="ignore", divide="ignore"):
            m = s / c if c else np.full(len(s), np.nan)
        return {"means": np.asarray(m), "rows": np.asarray([c])}
    if kind == "top_downtime":
        cnt, sums, _, _ = _split(tables["downtime_by_equipment"])
        down = sums[:, 0].astype(np.float64)
        order = np.lexsort((np.arange(len(down)), -down))[:arg]
        return {"ranked_downtime": down[order], "downtime": down,
                "uptime": sums[:, 1].astype(np.float64)}
    if kind == "production_rate":
        cnt, sums, mins, maxs = _split(tables["production_rate_windows"])
        return {"facts": cnt, "runtime_s": sums[:, 0], "oee_min": mins[:, 1],
                "oee_max": maxs[:, 1]}
    if kind == "production_curve":
        cnt, sums, mins, maxs = _split(tables["production_rate_windows"])
        return {"count": np.cumsum(cnt.astype(np.float64)),
                "sum": np.cumsum(sums.astype(np.float64), axis=0),
                "min": np.minimum.accumulate(mins, axis=0),
                "max": np.maximum.accumulate(maxs, axis=0)}
    if kind == "shift_report":
        cnt, sums, _, _ = _split(tables["kpi_by_unit_shift"])
        return {"count": cnt, "mean": _means(cnt, sums)}
    if kind == "kpi_rollup":
        cnt, sums, _, _ = _split(tables["oee_by_equipment"])
        return {"kpi_rollup": np.concatenate([sums, cnt[:, None]], axis=1)}
    if kind == "view":
        cnt, sums, mins, maxs = _split(tables[VIEW_ARGS[arg]])
        return {"count": cnt, "sum": sums, "mean": _means(cnt, sums),
                "min": mins, "max": maxs}
    raise KeyError(kind)


VIEW_ARGS = ("oee_by_equipment", "kpi_by_unit_shift", "downtime_by_equipment",
             "production_rate_windows")


def rel_err(got, want) -> float:
    """Largest |got - want| / max(|want|, 1) over the elements, where equal
    infinities and NaN against NaN count as agreement. A NaN or infinity on
    one side only is an infinite error."""
    g = np.asarray(got, np.float64).ravel()
    w = np.asarray(want, np.float64).ravel()
    if g.shape != w.shape:
        return float("inf")
    if not g.size:
        return 0.0
    same = (g == w) | (np.isnan(g) & np.isnan(w))
    finite = np.isfinite(g) & np.isfinite(w)
    if (~same & ~finite).any():
        return float("inf")
    with np.errstate(invalid="ignore"):
        err = np.where(same, 0.0,
                       np.abs(g - w) / np.maximum(np.abs(w), 1.0))
    return float(err.max())
