"""Reduction of a profiler trace to the numbers the per-layer metrics
read.

The JAX profiler writes `<dir>/plugins/profile/<run>/<host>.xplane.pb`;
`jax.profiler.ProfileData` reads it.  Device planes are named
``/device:TPU:<n>``; the operations the device ran are the events of
their ``XLA Ops`` line.  Host spans (`TraceAnnotation`) are events of
the ``/host:CPU`` plane, on the same clock.  Times are nanoseconds.
"""

from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field

import numpy as np

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def options():
    """Profiler options of a traced run: device activity and the host
    spans of `TraceAnnotation` (host tracer level 1), without the Python
    tracer, whose event per Python call would swamp a serving loop."""
    import jax
    o = jax.profiler.ProfileOptions()
    o.python_tracer_level = 0
    o.host_tracer_level = 1
    o.enable_hlo_proto = False
    return o


@dataclass
class Events:
    """Device operations and whole programs per device, and host spans
    by name."""
    ops: dict = field(default_factory=dict)     # device -> [(name, t0, t1)]
    spans: dict = field(default_factory=dict)   # span name -> [(t0, t1)]
    modules: dict = field(default_factory=dict)  # device -> [(name, t0, t1)]


def find(trace_dir: str) -> str:
    hits = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                            recursive=True))
    if not hits:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return hits[-1]


def load(path: str, span_prefix: str = "bench.") -> Events:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    ev = Events()
    for plane in pd.planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            got = {OPS_LINE: [], MODULES_LINE: []}
            for line in plane.lines:
                if line.name in got:
                    got[line.name] += [(e.name, e.start_ns,
                                        e.start_ns + e.duration_ns)
                                       for e in line.events]
            if got[OPS_LINE]:
                ev.ops[plane.name] = sorted(got[OPS_LINE],
                                            key=lambda o: o[1])
                ev.modules[plane.name] = sorted(got[MODULES_LINE],
                                                key=lambda o: o[1])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(span_prefix):
                        ev.spans.setdefault(e.name, []).append(
                            (e.start_ns, e.start_ns + e.duration_ns))
    return ev


def union(intervals) -> list:
    """Disjoint sorted union of [t0, t1) intervals."""
    out: list = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def overlap(a: list, b: list) -> float:
    """Total length of the intersection of two disjoint sorted unions."""
    i = j = 0
    tot = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            tot += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return tot


def busy_ns(ev: Events, window: tuple, within: list | None = None) -> float:
    """Device-busy nanoseconds inside `window` (and inside the host spans
    `within`, when given), averaged over the devices traced."""
    if not ev.ops:
        return 0.0
    clip = _clip(window, within)
    per = [overlap(_busy(ev, dev), clip) for dev in ev.ops]
    return float(np.mean(per))


def _busy(ev: Events, dev: str) -> list:
    """Union of the intervals in which the device ran an operation or a
    program."""
    return union([(t0, t1) for _, t0, t1 in ev.ops[dev]]
                 + [(t0, t1) for _, t0, t1 in ev.modules.get(dev, [])])


def summary(ev: Events, window: tuple) -> str:
    """One line per device: events read, and where the first and last
    fall against the window (seconds) — a trace that starts late shows
    here."""
    out = []
    for dev, ops in ev.ops.items():
        out.append(f"{dev}: {len(ops)} ops, {len(ev.modules.get(dev, []))} "
                   f"programs, first at {(ops[0][1] - window[0]) / 1e9:+.3f} s,"
                   f" last ends {(max(o[2] for o in ops) - window[1]) / 1e9:+.3f}"
                   f" s from the close")
    return "; ".join(out) or "no device events"


def whole_spans(ev: Events, name: str, window: tuple) -> list:
    """The host spans `name` that lie wholly inside `window`: the calls
    whose every device operation the trace can hold."""
    return [(a, b) for a, b in ev.spans.get(name, [])
            if a >= window[0] and b <= window[1]]


def _clip(window: tuple, within: list | None) -> list:
    if within is None:
        return [list(window)]
    return union([(max(a, window[0]), min(b, window[1])) for a, b in within
                  if min(b, window[1]) > max(a, window[0])])


def kernel_ns(ev: Events, pattern: str, window: tuple,
              within: list | None = None) -> float:
    """Summed device time of the operations whose name (the HLO
    instruction as the trace prints it) matches `pattern`, a regular
    expression, inside `window` (and the host spans `within`, when
    given), over all devices traced."""
    rx = re.compile(pattern)
    clip = _clip(window, within)
    return float(sum(overlap([[t0, t1]], clip)
                     for ops in ev.ops.values()
                     for name, t0, t1 in ops if rx.search(name)))


def short(name: str) -> str:
    """An operation's name without its shapes: ``%while.218``, with the
    target of a custom call (``%closed_call.10 tpu_custom_call``)."""
    head = name.split(" = ")[0]
    m = re.search(r'custom_call_target="([^"]+)"', name)
    return f"{head} {m.group(1)}" if m else head


def top_ops(ev: Events, window: tuple, n: int = 10) -> list:
    """The `n` operations that took the most device time, in seconds per
    device, by `short` name.  A loop's time includes the operations
    of its body, which are listed too."""
    tot: dict = {}
    for ops in ev.ops.values():
        for name, t0, t1 in ops:
            d = min(t1, window[1]) - max(t0, window[0])
            if d > 0:
                key = short(name)
                tot[key] = tot.get(key, 0.0) + d
    k = max(len(ev.ops), 1)
    return [[name, t / k / 1e9] for name, t in
            sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps(ev: Events, window: tuple, n: int = 10) -> list:
    """The `n` longest device-idle gaps inside `window`, each named by the
    host spans running during it (``idle`` when none), in seconds."""
    if not ev.ops:
        return []
    busy = union(iv for dev in ev.ops for iv in _busy(ev, dev))
    gaps, t = [], window[0]
    for a, b in busy:
        if a > t:
            gaps.append((t, min(a, window[1])))
        t = max(t, b)
        if t >= window[1]:
            break
    if t < window[1]:
        gaps.append((t, window[1]))
    gaps = sorted((g for g in gaps if g[1] > g[0]),
                  key=lambda g: g[0] - g[1])[:n]
    out = []
    for a, b in gaps:
        names = sorted({s for s, iv in ev.spans.items()
                        for x, y in iv if x < b and y > a
                        and s not in ("bench.window", "bench.open")})
        out.append([" + ".join(names) or "idle", (b - a) / 1e9])
    return out
