"""Serving metrics: per-op latency quantiles, batch occupancy, queue wait,
and the beam-loop counters of query batches.

Latency is measured enqueue→completion (queueing + padding + device
time), which is what a client of the engine actually observes.  Samples
are kept in bounded reservoirs so a long-running engine never grows
unboundedly; p50/p99 come from the retained sample.

Queue wait (enqueue → the pump popping the request's batch) and the
beam counters are raw sums beside the request and batch counts, so a
reader differences two snapshots for the mean over any interval.
"""

from __future__ import annotations

import collections
from typing import Deque, Dict

import numpy as np

from repro.serve.request import Op


class ServeMetrics:
    def __init__(self, *, reservoir: int = 16384):
        self._lat: Dict[Op, Deque[float]] = {
            op: collections.deque(maxlen=reservoir) for op in Op}
        self._count: Dict[Op, int] = {op: 0 for op in Op}
        self._batches: Dict[Op, int] = {op: 0 for op in Op}
        #: seconds the `count` requests of each op waited in the queue,
        #: summed: engine clock at the pop of a batch − each request's
        #: enqueue time
        self._queue_wait: Dict[Op, float] = {op: 0.0 for op in Op}
        #: the backend's beam-loop counters (`BeamCounters`), summed over
        #: the query batches that reported them (`batches`)
        self.beam = {"batches": 0, "trips": 0, "hops": 0, "slots": 0}
        #: the coalescing window each op is currently running under —
        #: with adaptive batch shaping this tracks the arrival-rate EMA
        #: (DESIGN.md §10); static configs just echo their constants
        self.windows: Dict[Op, float] = {op: 0.0 for op in Op}
        self.snapshot_resolves = 0
        self.maintenance_runs: Dict[str, int] = {
            "compact": 0, "reorder": 0, "consolidate": 0, "checkpoint": 0,
            "tier": 0}
        #: WAL accounting (zero when the engine runs without a WAL):
        #: records appended vs group commits actually fsync'd — the
        #: ratio is the group-commit amortization the config bought
        self.wal_records = 0
        self.wal_commits = 0
        #: deletes the engine dropped host-side as duplicates of an
        #: already-deleted external id or as never-allocated ids
        #: (relaxed coalescing can double-submit); the device-side
        #: count of absent-id no-ops lives on the backend stats surface
        #: (`VectorBackend.stats().delete_noops`)
        self.delete_noops = 0
        #: pumps that withheld pending write batches because an
        #: overlapped repair was in flight (relaxed mode; DESIGN.md §13)
        self.write_holds = 0

    def record_batch(self, op: Op, latencies, queue_wait: float) -> None:
        """One executed batch: each request's enqueue→completion latency,
        and the queue wait of its requests, summed."""
        self._count[op] += len(latencies)
        self._batches[op] += 1
        self._lat[op].extend(latencies)
        self._queue_wait[op] += queue_wait

    def record_beam(self, beam) -> None:
        """One query batch's `BeamCounters`."""
        self.beam["batches"] += 1
        self.beam["trips"] += beam.trips
        self.beam["hops"] += beam.hops
        self.beam["slots"] += beam.slots

    def _quantiles(self, op: Op):
        lat = np.asarray(self._lat[op], np.float64)
        if lat.size == 0:
            return {"p50_ms": 0.0, "p99_ms": 0.0}
        return {"p50_ms": float(np.percentile(lat, 50) * 1e3),
                "p99_ms": float(np.percentile(lat, 99) * 1e3)}

    def snapshot(self) -> dict:
        out: dict = {"snapshot_resolves": self.snapshot_resolves,
                     "delete_noops": self.delete_noops,
                     "write_holds": self.write_holds,
                     "maintenance": dict(self.maintenance_runs),
                     "wal": {"records": self.wal_records,
                             "commits": self.wal_commits},
                     "beam": dict(self.beam)}
        for op in Op:
            nb = self._batches[op]
            out[op.value] = {
                "count": self._count[op],
                "batches": nb,
                "mean_batch": round(self._count[op] / nb, 2) if nb else 0.0,
                "queue_wait_s": self._queue_wait[op],
                "window_ms": round(self.windows[op] * 1e3, 4),
                **{k: round(v, 3) for k, v in self._quantiles(op).items()},
            }
        return out
