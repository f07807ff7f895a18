"""Runtime layer: the share of trade CDC records reaching the load stage in
the window that it held in the late buffer (``load.held`` counter events:
a missing account version, or an earlier record of the same TradeID held),
over the records of ``load.queue_wait`` in the window, in percent. None
when the window holds no hand-off."""
from bench.harness import span_totals


def read(run):
    lo, hi = run.window
    held = sum((args or {}).get("value", 0)
               for ph, nm, _lane, t0, _dur, args in run.spans
               if ph == "C" and nm == "load.held" and lo <= t0 < hi)
    _, records, _ = span_totals(run, "load.queue_wait", "records")
    return 100.0 * held / records if records else None
