"""A thin proxy around the backend that `ServeEngine` is given.

It delegates every attribute, and around the calls into the layers
below the engine it records:

- the order in which the backend applied batches (`log`), from which
  the reference rebuilds the live set each query saw;
- host spans: `jax.profiler.TraceAnnotation` around each call, so the
  device trace can be cut by layer, and host-clock times.  With
  `sync=True` (the traced run) a write call ends in a device sync so
  its time is the device's; the untraced run adds no sync, so the
  engine runs exactly as it would without the benchmark;
- the backend's I/O counters around each search call (traced run only:
  reading them syncs).
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Optional

import jax
import numpy as np

clock = time.monotonic


def row_keys(xs: np.ndarray) -> list:
    """Hashable identity of each row (the bytes of the vector)."""
    return [r.tobytes() for r in np.ascontiguousarray(xs, np.float32)]


@dataclass
class Call:
    kind: str                 # search | insert | delete
    t0: float
    t1: float
    ids: Optional[np.ndarray] = None      # internal ids written / returned
    rows: Optional[np.ndarray] = None     # vectors inserted
    keys: list = field(default_factory=list)   # query row identities
    io: Optional[dict] = None             # IOStats delta (traced run)


class BackendProxy:
    def __init__(self, backend, *, sync: bool = False):
        self._b = backend
        self.sync_calls = sync
        self.log: list[Call] = []

    def __getattr__(self, name):
        return getattr(self._b, name)

    # -- helpers ---------------------------------------------------------------

    def _io(self) -> Optional[dict]:
        if not self.sync_calls:
            return None
        st = jax.device_get(self._b.io_stats)
        return {k: int(v) for k, v in st._asdict().items()}

    def _sync(self) -> None:
        if self.sync_calls:
            self._b.sync()

    # -- the calls the engine makes ----------------------------------------------

    def search(self, queries, k=None, *, params=None):
        io0 = self._io()
        t0 = clock()
        with jax.profiler.TraceAnnotation("bench.search"):
            res = self._b.search(queries, k, params=params)
        t1 = clock()
        io1 = self._io()
        io = None if io0 is None else {k_: io1[k_] - io0[k_] for k_ in io0}
        self.log.append(Call("search", t0, t1, ids=np.asarray(res.ids),
                             keys=row_keys(queries), io=io))
        return res

    def insert_batch(self, xs, *, pad_to=None):
        t0 = clock()
        with jax.profiler.TraceAnnotation("bench.insert"):
            res = self._b.insert_batch(xs, pad_to=pad_to)
            self._sync()
        self.log.append(Call("insert", t0, clock(),
                             ids=np.asarray(res.ids, np.int64),
                             rows=np.array(xs, np.float32, copy=True)))
        return res

    def delete_batch(self, ids, *, pad_to=None):
        t0 = clock()
        with jax.profiler.TraceAnnotation("bench.delete"):
            res = self._b.delete_batch(ids, pad_to=pad_to)
            self._sync()
        self.log.append(Call("delete", t0, clock(),
                             ids=np.array(ids, np.int64, copy=True)))
        return res
