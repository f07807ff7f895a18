"""Runtime layer: the queued hand-offs the transform stage merges into
one dispatch, on average: the mean `handoffs` argument of the
`transform.dispatch` spans that start in the window (1.0 where nothing
merges). A program whose spans carry no `handoffs` has nothing to read."""


def read(run):
    lo, hi = run.window
    got = [args["handoffs"] for ph, name, _lane, t0, _dur, args in run.spans
           if ph == "X" and name == "transform.dispatch" and lo <= t0 < hi
           and args and "handoffs" in args]
    return sum(got) / len(got) if got else None
