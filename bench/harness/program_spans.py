"""The program's own host spans (``lsmvec.`` prefix) in a traced run.

The program marks the host part of each search call with
`jax.profiler.TraceAnnotation`s, on the device trace's clock:
``lsmvec.search.dispatch`` (from entry to the return after the jit
call) and ``lsmvec.search.collect`` (the wait for, and fetch of, the
results).  `trace.load` keeps only the benchmark's ``bench.`` spans;
this reads the run's trace file once more, walking only its host
planes, and keeps the result for every reader of the run.  A program
that marks no such spans reads as an empty table.
"""

from __future__ import annotations

import os
import sys
import time

from harness import spec, trace

PREFIX = "lsmvec."
DISPATCH = "lsmvec.search.dispatch"
COLLECT = "lsmvec.search.collect"
TRACE_DIR = os.path.join(spec.ROOT, "bench_out", "trace")

_read: dict = {}           # trace file -> {span name: [(t0, t1)]}


def load(path: str) -> dict:
    """The ``lsmvec.`` spans of the host planes of `path`, by name, in
    nanoseconds."""
    from jax.profiler import ProfileData
    out: dict = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(PREFIX):
                    out.setdefault(e.name, []).append(
                        (e.start_ns, e.start_ns + e.duration_ns))
    return {k: sorted(v) for k, v in out.items()}


def spans(run) -> dict | None:
    """The program's spans in the run's trace; None in an untraced run
    or where no trace file is found."""
    if run.events is None:
        return None
    try:
        path = trace.find(TRACE_DIR)
    except FileNotFoundError:
        return None
    if path not in _read:
        t0 = time.monotonic()
        _read[path] = load(path)
        n = sum(len(v) for v in _read[path].values())
        print(f"program spans: {n} read in {time.monotonic() - t0:.2f} s",
              file=sys.stderr)
    return _read[path]


def outermost(iv: list) -> list:
    """The spans of one name that no other of them contains (a sharded
    backend's call holds each shard's spans of the same name)."""
    out: list = []
    for a, b in sorted(iv, key=lambda s: (s[0], -s[1])):
        if not out or a >= out[-1][1]:
            out.append((a, b))
    return out


def whole_calls(sp: dict, window: tuple) -> list:
    """(dispatch, collect) span pairs of the search calls that lie wholly
    inside `window`: each dispatch with the first collect after it."""
    collects = outermost(sp.get(COLLECT, []))
    calls, j = [], 0
    for d in outermost(sp.get(DISPATCH, [])):
        while j < len(collects) and collects[j][0] < d[1]:
            j += 1
        if j == len(collects):
            break
        c = collects[j]
        j += 1
        if d[0] >= window[0] and c[1] <= window[1]:
            calls.append((d, c))
    return calls


def host_idle_ns(ev: trace.Events, sp: dict, window: tuple) -> float | None:
    """Device-idle nanoseconds inside the dispatch and collect spans of
    each whole search call, per call; None without a whole call."""
    calls = whole_calls(sp, window)
    if not calls:
        return None
    within = [s for call in calls for s in call]
    held = sum(b - a for a, b in within)
    return (held - trace.busy_ns(ev, window, within=within)) / len(calls)
