"""Backend dispatch and collect (`core/index.py`, `core/distributed.py`):
device-idle time inside the program's own spans of a search call,
``lsmvec.search.dispatch`` (padding, the snapshot check, the jit call,
the I/O counter update) and ``lsmvec.search.collect`` (the wait, the
fetch of results and beam counters), per search call that lies wholly
inside the traced part of the window: span length minus the device's
busy time inside it.  This is the time the host holds the device idle
on each call."""

from harness import program_spans


def read(run):
    sp = program_spans.spans(run)
    if not sp:
        return None
    ns = program_spans.host_idle_ns(run.events, sp, run.window_ns)
    return None if ns is None else ns / 1e6
