"""Online serving subsystem: continuous micro-batching over LSM-VEC.

The first layer of the repo that owns *time* (DESIGN.md §8): everything
under `repro.core` is pure functions over index state; this package
schedules an interleaved query/insert/delete stream onto them as
fixed-shape micro-batches with snapshot-cached reads and
threshold-driven maintenance.  The whole package programs against the
`VectorBackend` protocol (DESIGN.md §10) — single-device and sharded
backends serve through the identical code path.

- request    — Op/Request/Ticket plumbing
- queue      — arrival-ordered coalescing queue (strict/relaxed modes)
- scheduler  — ServeEngine: pad-and-mask dispatch, snapshot lifecycle,
  external-id ownership, adaptive batch shaping
- metrics    — p50/p99 latency, occupancy, queue wait, beam-loop
  counters, chosen windows
- maintenance— tombstone/heat thresholds -> consolidate()/compact()/
  reorder(), applied per shard (lazy-delete consolidation: DESIGN.md §9)
- wal        — group-committed write-ahead log; with `ServeConfig.wal`
  set, acks imply durability and `ServeEngine.recover` restores the
  latest covering checkpoint + replays the tail (DESIGN.md §11)
"""

from repro.serve.maintenance import MaintenanceManager, MaintenancePolicy
from repro.serve.metrics import ServeMetrics
from repro.serve.queue import CoalescingQueue
from repro.serve.request import Op, QueryResult, Request, Ticket
from repro.serve.scheduler import ServeConfig, ServeEngine
from repro.serve.wal import WalConfig, WalRecord, WriteAheadLog

__all__ = [
    "Op", "QueryResult", "Request", "Ticket", "CoalescingQueue",
    "ServeMetrics", "MaintenancePolicy", "MaintenanceManager",
    "ServeConfig", "ServeEngine", "WalConfig", "WalRecord",
    "WriteAheadLog",
]
