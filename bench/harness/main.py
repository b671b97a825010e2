"""One run of one cell: set up, measure for `--seconds`, check, print.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The last line of stdout is the result object; the last lines of stderr
are the numbers compared, each beside its limit.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import shutil
import sys
import threading
import time
from dataclasses import dataclass

import jax
import numpy as np

from harness import check, device, peaks, spec, trace
from harness.data import Deployment
from harness.proxy import BackendProxy
from harness.serve import closed_loop

clock = time.monotonic
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
OUT = os.path.join(spec.ROOT, "bench_out")      # git-ignored, fixed
# set-up's query batches: the second loads a program the first does not,
# which every batch of the window runs
WARM_BATCHES = 2
# where the traced part of a window starts, in search calls before
# `--seconds`: more than one, so that the call that ends at the close
# lies wholly inside it even when it runs a little longer than the call
# before it
TRACE_CALLS = 1.25


class Tracer:
    """Profiles the last part of the window: started by the client loop
    `TRACE_CALLS` times the search call that ended last (`call_s`, read
    as the window goes on) before `seconds`, stopped at the close, the
    first answer at or after `seconds`; so the part holds 1.25 to 2.25
    search calls, whole calls among them for the per-layer readers.  It
    is no longer because the chip's trace buffer holds about 6.3 million
    operations (~50 s of this program's beam loop), and writing a trace
    out takes some 30 us per operation, which a traced run pays inside
    its time limit."""

    def __init__(self, trace_dir: str, seconds: float, call_s):
        self.dir = trace_dir
        self.seconds = seconds
        self.call_s = call_s
        self.t_start = None      # clock() when the trace started
        self.t_stop = None
        self.stop_s = 0.0        # seconds stop_trace took

    def maybe_start(self, elapsed: float) -> None:
        if self.t_start is None and \
                elapsed >= self.seconds - TRACE_CALLS * self.call_s():
            jax.profiler.start_trace(self.dir,
                                     profiler_options=trace.options())
            self.t_start = clock()
            with jax.profiler.TraceAnnotation("bench.open"):
                pass

    def stop(self) -> None:
        if self.t_start is not None and self.t_stop is None:
            self.t_stop = clock()
            jax.profiler.stop_trace()
            self.stop_s = clock() - self.t_stop


class Compiles:
    """Programs compiled, and seconds spent compiling, as JAX reports
    them (from whichever thread compiles)."""

    def __init__(self):
        self.n = 0
        self.s = 0.0
        self._lock = threading.Lock()
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, secs: float, **_):
        if event == COMPILE_EVENT:
            with self._lock:
                self.n += 1
                self.s += secs


@dataclass
class Run:
    """What a per-layer reader may read."""
    cell: spec.Cell
    window: object                 # serve.Window
    log: list                      # proxy.Call, whole run
    log_window: list               # proxy.Call inside the window
    log_traced: list               # ... of those, ended inside the trace
    served: list                   # query requests answered in the window
    metrics0: dict                 # ServeMetrics snapshots around the window
    metrics1: dict
    setup: dict
    device_kind: str
    events: object = None          # trace.Events (traced run)
    window_ns: tuple = (0.0, 0.0)

    @property
    def peaks(self) -> dict:
        return peaks.peak(self.device_kind)


def enable_compile_cache() -> str:
    """The repository's persistent cache (`JAX_COMPILATION_CACHE_DIR`, or
    a fixed directory inside the checkout), keeping every program."""
    from repro.compile_cache import enable_compile_cache as enable
    d = enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return d


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def p95_ms(lat: list) -> float:
    return float(np.percentile(np.asarray(lat), 95) * 1e3) if lat \
        else float("nan")


class Setup:
    """Builds the index, the engine and the warm state of a cell."""

    def __init__(self, cell: spec.Cell, seed: int, compiles: Compiles,
                 sizes: dict):
        from repro.core import hnsw
        from repro.core.index import LSMVecIndex
        from repro.serve import MaintenancePolicy, ServeConfig, ServeEngine
        from repro.serve.wal import WalConfig

        c, t = cell.config, cell.traffic
        self.times = {}
        sv = c["serve"]
        batch = sv["query_batch"]
        t0 = clock()
        self.dep = Deployment(c, seed, n_base=sizes.get("n_base", c["n_base"]),
                              n_queries=t["query_pool"],
                              n_warm=WARM_BATCHES * batch)
        self.times["synth_s"] = clock() - t0

        ix = c["index"]
        cfg = hnsw.HNSWConfig(cap=sizes.get("cap", ix["cap"]), dim=c["dim"],
                              M=ix["M"], ef_search=ix["ef_search"],
                              ef_construction=ix["ef_construction"], k=c["k"])
        t0 = clock()
        c0 = compiles.s
        backend = LSMVecIndex.build(cfg, self.dep.base,
                                    seed=self.dep.build_seed)
        backend.sync()
        self.times["build_s"] = clock() - t0
        self.times["build_compile_s"] = compiles.s - c0

        self.proxy = BackendProxy(backend, sync=False)
        wal_dir = os.path.join(OUT, "wal")
        shutil.rmtree(wal_dir, ignore_errors=True)
        wal = sv["wal"]
        mp = sv["maintenance"]
        self.engine = ServeEngine(self.proxy, ServeConfig(
            query_batch=batch,
            insert_batch=sv["insert_batch"],
            delete_batch=sv["delete_batch"],
            strict_order=sv["strict_order"],
            wal=WalConfig(dir=wal_dir, group_commit_n=wal["group_commit_n"],
                          group_commit_ms=wal["group_commit_ms"],
                          sync=wal["sync"]),
            maintenance=MaintenancePolicy(
                consolidate_ratio=mp["consolidate_ratio"],
                check_every=mp["check_every"])))

        # the window's shapes, through the engine: whole query batches
        t0 = clock()
        wq = self.dep.warm_queries
        for i in range(0, len(wq), batch):
            tickets = [self.engine.submit_query(q) for q in wq[i:i + batch]]
            self.engine.drain()
            for tk in tickets:
                tk.result(0)          # a failed set-up step raises here
        backend.sync()
        self.times["warm_s"] = clock() - t0


def read_layers(run: Run) -> dict:
    """The cell's per-layer metrics.  One that finds nothing to read is
    left out of the result line, which the check refuses for a metric
    the cell lists, and is named as an error on stderr."""
    rec = {}
    for m in run.cell.per_layer:
        v = spec.reader(m["name"])(run)
        if v is None:
            print(f"error: per-layer metric {m['name']} is listed for "
                  f"{run.cell.name} and found nothing to read",
                  file=sys.stderr)
        else:
            rec[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return rec


def window_ns(events: trace.Events, t0: float, t1: float,
              entered: float) -> tuple:
    """The traced window [t0, t1] (host clock) on the trace's clock,
    anchored on the ``bench.open`` span that starts at `entered`."""
    span = events.spans.get("bench.open")
    if not span:
        raise RuntimeError("the trace holds no bench.open span")
    s0 = span[0][0]
    return (s0 + (t0 - entered) * 1e9, s0 + (t1 - entered) * 1e9)


def main(argv=None, *, start: float | None = None,
         require=device.require_chips, sizes: dict | None = None,
         fault=None) -> int:
    """`require`, `sizes` and `fault` exist for the harness's own tests
    on the CPU: such a run skips no step of a measured run."""
    start = clock() if start is None else start
    args = parse(argv)
    cell = spec.load(args.workload)
    devs = require(cell.chips)
    kind = devs[0].device_kind
    if require is device.require_chips:
        peaks.peak(kind)               # an unknown chip stops the run here
    cache = enable_compile_cache()
    compiles = Compiles()
    print(f"bench: {cell.name} seed {args.seed} on {kind} x {len(devs)}; "
          f"compile cache {cache}", file=sys.stderr)

    su = Setup(cell, args.seed, compiles, sizes or {})
    if fault is not None:
        fault(su.proxy)
    eng, proxy = su.engine, su.proxy
    proxy.sync_calls = bool(args.trace)
    setup_compile_s, setup_compiles = compiles.s, compiles.n
    m0 = eng.metrics.snapshot()
    i0 = len(proxy.log)
    t = cell.traffic

    trace_dir = os.path.join(OUT, "trace")
    tracer = None
    if args.trace:
        shutil.rmtree(trace_dir, ignore_errors=True)

        def last_call_s() -> float:
            c = next(c for c in reversed(proxy.log) if c.kind == "search")
            return c.t1 - c.t0
        tracer = Tracer(trace_dir, args.seconds, last_call_s)
    eng.start()
    win = closed_loop(eng, su.dep.queries, t["clients"], args.seconds,
                      tracer)
    eng.stop(drain=True)
    if tracer is not None:
        tracer.stop()                  # if the window closed before it
    window_compiles = compiles.n - setup_compiles
    setup_s = win.t0 - start

    reqs = win.requests
    failed = [r for r in reqs if r.done is None or r.error is not None]
    queries = [r for r in reqs if r.done is not None and r.error is None]
    in_window = [r for r in queries if r.done <= win.t1]
    m1 = eng.metrics.snapshot()
    # the engine's external -> internal ids of every id served
    served_ext = np.unique(np.concatenate(
        [np.asarray(r.value.ids, np.int64) for r in queries] or
        [np.zeros(0, np.int64)]))
    ext2int = {int(e): eng.resolve_ext(int(e)) for e in served_ext if e >= 0}
    mem = device.peak_bytes(devs)
    eng.close()
    log = proxy.log
    in_win = [c for c in log[i0:] if c.t0 <= win.t1]
    traced = (tracer.t_start, min(win.t1, tracer.t_stop)) if tracer \
        else (win.t0, win.t1)
    run = Run(cell, win, log, in_win,
              [c for c in in_win if c.t0 >= traced[0] and c.t1 <= traced[1]],
              in_window, m0, m1,
              {**su.times, "compile_s": setup_compile_s,
               "compiles": setup_compiles, "setup_s": setup_s},
              kind)
    base = su.dep.base
    proxy._b = su.engine = su.proxy = eng = None   # free the index
    gc.collect()

    # the per-layer readings (traced run), then the reference
    rec = {}
    breakdown = None
    dev = device.record(devs)
    dev["memory_peak_bytes"] = mem
    if args.trace:
        t_read = clock()
        run.events = trace.load(trace.find(trace_dir))
        run.window_ns = window_ns(run.events, traced[0], traced[1],
                                  tracer.t_start)
        busy = trace.busy_ns(run.events, run.window_ns)
        dev["busy_s"] = busy / 1e9
        dev["window_s"] = (run.window_ns[1] - run.window_ns[0]) / 1e9
        rec = read_layers(run)
        breakdown = {"device_ops": trace.top_ops(run.events, run.window_ns),
                     "idle_gaps": trace.idle_gaps(run.events, run.window_ns)}
        print(f"trace: last {traced[1] - traced[0]:.1f} s of the window; "
              f"stop {tracer.stop_s:.1f} s, read {clock() - t_read:.1f} s; "
              f"{trace.summary(run.events, run.window_ns)}", file=sys.stderr)

    ans = check.answers(ext2int, log, base, queries, cell.config["k"])
    nums = check.numbers(cell.config, ans, unanswered=len(failed))
    correct = all(n.ok for n in nums)
    if not args.trace:
        e2e = {
            "queries_per_s": len(in_window) / (win.t1 - win.t0),
            "query_p95_ms": p95_ms([r.latency for r in in_window]),
            "recall_at_10": next(n.value for n in nums
                                 if n.name == "recall_at_10"),
            "setup_s": setup_s,
        }
        for m in cell.end_to_end:
            if np.isfinite(e2e[m["name"]]):
                rec[m["name"]] = {"value": float(e2e[m["name"]]),
                                  "unit": m["unit"]}
    for line in (f"setup: {json.dumps(run.setup)}",
                 f"window: {win.t1 - win.t0} s, {len(reqs)} requests, "
                 f"{len(in_window)} queries answered in it, "
                 f"{window_compiles} programs compiled inside it"):
        print(line, file=sys.stderr)
    for n in nums:
        print(n.line(), file=sys.stderr)
    out = {"correct": correct, "attempted": len(reqs), "failed": len(failed),
           "metrics": rec, "device": dev}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = {n.name: {"value": n.value if np.isfinite(n.value)
                              else None, n.rule: n.limit} for n in nums}
    sys.stdout.flush()
    print(json.dumps(out), flush=True)
    return 0
