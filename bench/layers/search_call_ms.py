"""Backend dispatch and collect (`core/index.py`): mean host-clock time
of one search call in the window, from the call to its collected
result (the call ends in the device sync of `collect`)."""


def read(run):
    t = [c.t1 - c.t0 for c in run.log_window if c.kind == "search"]
    return sum(t) / len(t) * 1e3 if t else None
