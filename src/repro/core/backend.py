"""The index↔serve boundary: `VectorBackend` protocol + typed results.

Everything above the functional core (`repro.serve`, benchmarks,
examples) programs against this protocol instead of a concrete index
class (DESIGN.md §10).  Two implementations ship:

- `LSMVecIndex` (`core/index.py`) — the single-device index;
- `ShardedBackend` (`core/distributed.py`) — hash-partitioned shards,
  each a full `LSMVecIndex`, fan-out search with device-side local
  top-k and a host merge.

The id contract: a backend exposes one flat *internal* id space
`[0, cap)` (for shards, block-encoded `shard * shard_cap + local`).
Internal ids are retired, never reused (consolidation), and only ever
permuted by `reorder`, which returns the permutation so a serving layer
can fold it into its own external↔internal map.  External ids — the ids
clients hold — are owned entirely by the serving layer; the backend
never sees them.

Search is two-phase (DESIGN.md §13): `dispatch_search` enqueues the
device work and returns a `SearchHandle` without forcing a host sync;
`handle.collect()` blocks on the device arrays and produces the final
`SearchResult`.  `search` = dispatch + collect, so single-call sites
are unchanged and shards=1 stays bit-parity.  Maintenance is unified
behind `maintain(op, **params) -> MaintenanceReport`, with an optional
async pair `begin_maintain`/`poll_maintain` for overlapped
consolidation.

Typed results replace the ad-hoc tuple/list returns: `search` returns a
`SearchResult`, `insert_batch`/`delete_batch` return an `UpdateResult`.
Both are frozen value types — the PR-4 sequence-compat shims are gone;
use `.ids`/`.dists` explicitly.
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Optional, Protocol, Sequence, runtime_checkable

import numpy as np


@dataclass(frozen=True)
class BeamCounters:
    """What one search batch's beam loop did on the device.

    `trips`: trips the batch's loop ran (it runs until its slowest lane
    stops); `hops`: nodes expanded, summed over the batch's queries;
    `slots`: expansions the loop had room for — trips x lanes dispatched
    (padded lanes included) x nodes popped per trip.  `hops / slots` is
    the share of the loop's lanes that did work.
    """

    trips: int
    hops: int
    slots: int


@dataclass(frozen=True)
class SearchResult:
    """Batched ANN search result in the backend's internal id space.

    `ids` int [B, k] (-1 pads under-full rows), `dists` f32 [B, k]
    (squared L2, +inf on pads).  `beam`: the batch's beam-loop counters,
    where the backend reports them.
    """

    ids: np.ndarray
    dists: np.ndarray
    beam: Optional[BeamCounters] = None


@dataclass(frozen=True)
class UpdateResult:
    """Result of a batched mutation.

    For inserts, `ids` holds the new internal ids in submission order;
    for deletes, the internal ids the batch targeted (−1 = masked pad).
    `n_applied` counts items the backend dispatched (inserts allocated;
    deletes with a routable non-negative id).  Dispatched deletes that
    turn out to be device-side no-ops (absent/already-dead ids) are NOT
    subtracted here — they are reported once, in
    `stats().delete_noops`, so the two counts never drift.
    """

    ids: np.ndarray
    n_applied: int


@dataclass(frozen=True)
class SearchParams:
    """Typed search knobs — the one place defaults are resolved.

    A `None` field means "use the backend config default" (resolved via
    `resolve(cfg)` at the dispatch boundary, nowhere else).
    `record_heat=None` defers to the caller's policy: `LSMVecIndex`
    resolves it to True, `ServeEngine` resolves it from its tier policy.
    `use_snapshot` selects the cached dense-read snapshot (serving
    path); `pad_to` pads the query batch to a fixed traced width.
    """

    rho: Optional[float] = None
    ef: Optional[int] = None
    use_filter: Optional[bool] = None
    n_expand: Optional[int] = None
    record_heat: Optional[bool] = None
    use_snapshot: bool = False
    pad_to: Optional[int] = None

    def resolve(self, cfg) -> "SearchParams":
        """Fill `None` knobs from an `HNSWConfig` — the single
        config-derived-defaults site for the whole stack."""
        return SearchParams(
            rho=float(cfg.rho if self.rho is None else self.rho),
            ef=int(cfg.ef_search if self.ef is None else self.ef),
            use_filter=bool(cfg.use_filter if self.use_filter is None
                            else self.use_filter),
            n_expand=int(cfg.n_expand if self.n_expand is None
                         else self.n_expand),
            record_heat=(True if self.record_heat is None
                         else bool(self.record_heat)),
            use_snapshot=bool(self.use_snapshot),
            pad_to=self.pad_to,
        )

    def replace(self, **kw) -> "SearchParams":
        return dataclasses.replace(self, **kw)


@dataclass(frozen=True)
class MaintenanceReport:
    """Uniform result of one `maintain(op)` invocation.

    `applied` is False when the op's own trigger rule declined to run
    (e.g. consolidate below the tombstone-ratio threshold).
    `reclaimed` — tombstone slots spliced out (consolidate);
    `perm` — internal-id permutation applied (reorder), else None;
    `demoted`/`promoted` — tier lane moves (tier).  `detail` carries
    op-specific extras (per-shard counts etc.).
    """

    op: str
    applied: bool
    reclaimed: int = 0
    perm: Optional[np.ndarray] = None
    demoted: int = 0
    promoted: int = 0
    detail: dict = field(default_factory=dict)


@runtime_checkable
class SearchHandle(Protocol):
    """An in-flight search: device work dispatched, host sync deferred.

    `collect()` blocks on the device arrays and returns the final
    `SearchResult`; it is called exactly once.  `is_ready()` is a
    non-blocking poll (True once every underlying device array has
    resolved — advisory, collect() is always safe).
    """

    def collect(self) -> SearchResult: ...

    def is_ready(self) -> bool: ...


@dataclass(frozen=True)
class MemoryBreakdown:
    """Per-component resident-byte accounting (DESIGN.md §12).

    Every field is bytes except the trailing lane counts.  `hot_vectors`
    is the dense f32 lane (with tiering off, every routable node is in
    it — the dense baseline fig6 compares against); `cold_codes` is the
    int8 + per-row-scale lane.  The serving-state components the old
    accounting omitted — tombstone lane, insert-overlay staging buffers,
    and the ext↔int id maps a serving layer must hold 1:1 with backend
    capacity — are included so fig6 numbers are honest about the full
    stack, not just the index arrays.  Adding two breakdowns adds
    componentwise (shard aggregation).
    """

    hot_vectors: int = 0     # dense-lane f32 rows
    cold_codes: int = 0      # int8 rows + f32 per-row scales
    upper_graph: int = 0     # upper-layer adjacency arrays
    upper_vec_cache: int = 0  # upper-node f32 rows cached for descent
    simhash_codes: int = 0   # per-node simhash codes (both lanes)
    memtable: int = 0        # LSM memtable (keys + rows + valid lane)
    tombstones: int = 0      # lazy-delete bitmap (capacity-sized)
    insert_overlay: int = 0  # insert_batch staging overlay (rows + valid)
    id_maps: int = 0         # serving ext↔int int64 maps (2 x cap)
    misc: int = 0            # entry/counters/rng etc.
    n_hot: int = 0           # dense-lane row count (not bytes)
    n_cold: int = 0          # cold-lane row count (not bytes)

    _BYTE_FIELDS = ("hot_vectors", "cold_codes", "upper_graph",
                    "upper_vec_cache", "simhash_codes", "memtable",
                    "tombstones", "insert_overlay", "id_maps", "misc")

    @property
    def total(self) -> int:
        return sum(getattr(self, f) for f in self._BYTE_FIELDS)

    def __add__(self, other: "MemoryBreakdown") -> "MemoryBreakdown":
        kw = {f: getattr(self, f) + getattr(other, f)
              for f in self._BYTE_FIELDS + ("n_hot", "n_cold")}
        return MemoryBreakdown(**kw)

    def as_dict(self) -> dict:
        d = {f: int(getattr(self, f)) for f in
             self._BYTE_FIELDS + ("n_hot", "n_cold")}
        d["total"] = int(self.total)
        return d


@dataclass(frozen=True)
class ShardStats:
    """Per-shard slice of `BackendStats`."""

    size: int            # live (returnable) nodes
    n_tombstones: int    # lazily deleted, not yet consolidated
    delete_noops: int    # device-counted deletes of absent/dead ids
    n_hot: int = 0       # dense-lane rows (== size+tombstones, tier off)
    n_cold: int = 0      # quantized-lane rows

    @property
    def tombstone_ratio(self) -> float:
        return self.n_tombstones / max(self.size + self.n_tombstones, 1)


@dataclass(frozen=True)
class BackendStats:
    """The backend stats surface — the single source for serving
    metrics (`ServeEngine.delete_noops` reads the device-side no-op
    count from here, never from a parallel accessor, so the two counts
    cannot drift).  `max_tombstone_ratio` is the per-shard maximum: the
    maintenance trigger fires when *any* shard crosses the threshold,
    not only when the global average does.
    """

    size: int
    n_tombstones: int
    delete_noops: int
    max_tombstone_ratio: float
    shards: tuple = ()     # tuple[ShardStats, ...], one entry per shard
    # per-component resident bytes, aggregated across shards (None only
    # for legacy constructors that predate the tier accounting)
    memory: Optional[MemoryBreakdown] = None


@runtime_checkable
class VectorBackend(Protocol):
    """What the serving layer requires of an index.

    Reads: `dispatch_search(queries, k, params=...)` enqueues device
    work and returns a `SearchHandle`; `search` is the one-call
    dispatch+collect.  Mutations: `insert_batch` / `delete_batch` take
    `pad_to` so a fixed micro-batch width dispatches through one traced
    shape.  Maintenance: `maintain(op, **params)` covers
    consolidate/compact/reorder/tier uniformly and returns a
    `MaintenanceReport`; `begin_maintain`/`poll_maintain` run a
    consolidation overlapped with serving (double-buffered repair,
    atomic cutover — DESIGN.md §13).  `initial_ids` seeds an
    external-id map: internal ids in allocation order for every node
    allocated so far.
    """

    @property
    def cap(self) -> int: ...                 # total internal id space

    @property
    def lazy_delete(self) -> bool: ...

    @property
    def snapshot_stale(self) -> bool: ...     # next snapshot read re-resolves

    def search(self, queries, k: Optional[int] = None, *,
               params: Optional[SearchParams] = None) -> SearchResult: ...

    def dispatch_search(self, queries, k: Optional[int] = None, *,
                        params: Optional[SearchParams] = None
                        ) -> SearchHandle: ...

    def insert_batch(self, xs, *,
                     pad_to: Optional[int] = None) -> UpdateResult: ...

    def delete_batch(self, ids, *,
                     pad_to: Optional[int] = None) -> UpdateResult: ...

    def maintain(self, op: str, **params) -> MaintenanceReport: ...

    # -- overlapped consolidation (DESIGN.md §13) -----------------------------
    # `begin_maintain("consolidate", ...)` starts a double-buffered repair
    # against a clone of the live state and returns True iff one was
    # started (False: trigger declined, or a repair is already in
    # flight).  Queries keep serving from the live snapshot;
    # `poll_maintain()` cuts over atomically once the repair's device
    # work is done and returns its report (None while still running or
    # when nothing is in flight; `block=True` forces completion).
    # Mutations barrier on any in-flight repair, so the cutover always
    # lands on a write-batch boundary — the WAL replay invariant.
    def begin_maintain(self, op: str, **params) -> bool: ...

    def poll_maintain(self, *, block: bool = False
                      ) -> Optional[MaintenanceReport]: ...

    def stats(self) -> BackendStats: ...

    def memory_bytes(self) -> int: ...        # MemoryBreakdown total

    def heat_total(self) -> int: ...

    def reset_heat(self) -> None: ...

    def initial_ids(self) -> np.ndarray: ...

    def trace_counts(self) -> dict: ...

    def sync(self) -> None: ...               # block until device work done

    # -- durability (DESIGN.md §11) -------------------------------------------
    # `save` writes an atomic full-state checkpoint (staged dir + rename)
    # whose manifest records `lsn`, the WAL position it covers: recovery
    # restores the checkpoint and replays only records with LSN > lsn.
    # `extra` carries caller-owned arrays (the serve engine's ext↔int id
    # map and deleted mask) and `meta` caller scalars; both come back
    # verbatim from the implementation's matching classmethod
    #   restore(cfg, ckpt_dir, ...) -> (backend, metadata, extras)
    # (a constructor, so not part of the instance protocol).  A restore
    # must refuse layout mismatches — cap/dim/shard count — rather than
    # load silently into a backend that would route differently.
    def save(self, ckpt_dir: str, *, lsn: int = 0,
             extra: Optional[dict] = None, meta: Optional[dict] = None,
             keep: int = 3, _pre_publish=None) -> str: ...


def merge_topk(gids: Sequence[np.ndarray], dists: Sequence[np.ndarray],
               k: int) -> SearchResult:
    """Host-side top-k merge of per-shard results.

    Each shard contributes its device-side local top-k (`gids[s]`
    int [B, k_s] already in the global id space, -1 pads; `dists[s]`
    f32 with +inf on pads).  Rows are distance-sorted per shard, so the
    merged stable sort is a deterministic P-way merge: ties resolve to
    the lower shard index, and with one shard the merge is the
    identity — the bit-parity anchor for shards=1.
    """
    flat_i = np.concatenate(gids, axis=1)
    flat_d = np.concatenate(dists, axis=1)
    flat_d = np.where(flat_i >= 0, flat_d, np.inf)
    order = np.argsort(flat_d, axis=1, kind="stable")[:, :k]
    return SearchResult(
        ids=np.take_along_axis(flat_i, order, axis=1),
        dists=np.take_along_axis(flat_d, order, axis=1))


def shard_of_seq(seq, n_shards: int):
    """Hash-partitioned routing: allocation sequence number -> shard.

    Fibonacci (multiplicative) hashing of the global allocation counter:
    deterministic across runs, load-balanced for any arrival pattern,
    and independent of vector content (content-hash routing would
    correlate shard load with the data distribution).  `seq` may be an
    int or an int array; one shard always routes to 0.
    """
    if n_shards == 1:
        return np.zeros_like(np.asarray(seq)) if np.ndim(seq) else 0
    x = np.asarray(seq, np.uint64) * np.uint64(0x9E3779B97F4A7C15)
    return ((x >> np.uint64(33)) % np.uint64(n_shards)).astype(np.int64)
