"""Graph search on the device (`core/hnsw.py`, `core/traversal.py`: the
upper descent, the beam loop and the kernels it calls): device-busy
time inside the search calls that lie wholly inside the traced part of
the window, per query those calls carried.  Nearly all of it is the
beam loop; a search that is cheaper on the device reads lower here
whatever implements it."""

from harness import trace


def read(run):
    calls = [c for c in run.log_traced if c.kind == "search"]
    if run.events is None or not calls:
        return None
    spans = trace.whole_spans(run.events, "bench.search", run.window_ns)
    busy = trace.busy_ns(run.events, run.window_ns, within=spans)
    n = sum(len(c.keys) for c in calls)
    return busy / 1e6 / n if busy > 0 and n else None
