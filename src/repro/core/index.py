"""LSMVecIndex — the public API of the paper's system.

Wraps the functional core (hnsw/lsm/traversal/simhash/reorder) behind the
interface a vector database exposes: build, insert, delete, search,
maintenance (reorder/compact), plus the I/O statistics and memory
accounting the paper's experiments report.
"""

from __future__ import annotations

import functools
from types import SimpleNamespace
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import ckpt
from repro.core import hnsw, iostats, lsm, reorder
from repro.core.backend import (
    BackendStats,
    MaintenanceReport,
    MemoryBreakdown,
    SearchParams,
    SearchResult,
    ShardStats,
    UpdateResult,
)
from repro.core.iostats import CostModel, IOStats
from repro.core.sentinel import declared_sync
from repro.kernels.l2_distance.ops import l2_distance
from repro.tier import policy as tier_policy


def brute_force_knn(vectors: jax.Array, queries: jax.Array, k: int,
                    live: Optional[jax.Array] = None,
                    block: int = 1024) -> np.ndarray:
    """Exact ground-truth ids [Q, k] (for Recall K@K evaluation)."""
    outs = []
    q = jnp.asarray(queries)
    for s in range(0, q.shape[0], block):
        d = l2_distance(q[s:s + block], vectors)
        if live is not None:
            d = jnp.where(live[None, :], d, jnp.inf)
        _, idx = jax.lax.top_k(-d, k)
        outs.append(np.asarray(idx))
    return np.concatenate(outs, axis=0)


def recall_at_k(found_ids: np.ndarray, true_ids: np.ndarray,
                block: int = 4096) -> float:
    """Recall K@K (Eq. 3): |found ∩ truth| / K averaged over queries.

    One broadcast membership test per block of queries instead of a
    per-query Python set loop (O(Q·k) host work that dominated eval at
    large Q).  Counting from the truth side — a truth id is hit if it
    appears anywhere in the found row — matches set-intersection
    semantics exactly: truth ids are distinct, and -1 pads in `found`
    never match.
    """
    f = np.asarray(found_ids)
    t = np.asarray(true_ids)
    k = t.shape[1]
    f = f[:, :k]
    hits = 0
    for s in range(0, len(t), block):
        fb, tb = f[s:s + block], t[s:s + block]
        hits += int((fb[:, :, None] == tb[:, None, :]).any(axis=1).sum())
    return hits / (k * len(t))


def jit_programs(cfg: hnsw.HNSWConfig) -> SimpleNamespace:
    """The jitted device programs of one `LSMVecIndex` config: every
    entry point an index dispatches, built once per index.  Module-level
    so that a caller can also lower them against shapes alone (the
    ahead-of-time TPU compile tests do)."""
    @functools.partial(jax.jit, donate_argnums=0)
    def _insert(state, x, key):
        return hnsw.insert(cfg, state, x, key)

    @functools.partial(jax.jit, donate_argnums=0)
    def _insert_batch(state, xs, keys, valid):
        return hnsw.insert_batch(cfg, state, xs, keys, valid=valid)

    @functools.partial(jax.jit, donate_argnums=0)
    def _delete(state, i):
        return hnsw.delete(cfg, state, i)

    @functools.partial(jax.jit, donate_argnums=0)
    def _delete_batch(state, ids):
        return hnsw.delete_batch(cfg, state, ids)

    @functools.partial(jax.jit, donate_argnums=(0, 4))
    def _insert_batch_snap(state, xs, keys, valid, snap):
        state, st, (orows, ovalid) = hnsw.insert_batch(
            cfg, state, xs, keys, valid=valid, return_overlay=True)
        snap = jnp.where(ovalid[:cfg.cap, None], orows[:cfg.cap], snap)
        return state, st, snap

    @functools.partial(jax.jit, donate_argnums=0)
    def _consolidate(state):
        return hnsw.consolidate(cfg, state)

    # non-donated: the live state keeps serving queries while the
    # repair computes against it (double-buffer, DESIGN.md §13)
    @jax.jit
    def _consolidate_bg(state):
        return hnsw.consolidate(cfg, state)

    # `record_heat` is static: False drops the scatter-add (and, on
    # the fused path, the loop's heat carries) from the trace —
    # callers that never apply heat don't pay for recording it
    @functools.partial(jax.jit, static_argnames=("rho", "use_filter",
                                                 "ef", "n_expand",
                                                 "record_heat"))
    def _search(state, qs, rho, use_filter, ef, n_expand,
                record_heat=True):
        res = hnsw.search_batch(cfg, state, qs, rho=rho,
                                use_filter=use_filter, ef=ef,
                                n_expand=n_expand)
        heat_delta = _heat_delta(state, res) if record_heat \
            else jnp.zeros_like(state.heat)
        return res, heat_delta

    @functools.partial(jax.jit, static_argnames=("rho", "use_filter",
                                                 "ef", "n_expand",
                                                 "record_heat"))
    def _search_snap(state, qs, valid, snap, rho, use_filter, ef,
                     n_expand, record_heat=True):
        res = hnsw.search_batch(cfg, state, qs, rho=rho,
                                use_filter=use_filter, ef=ef,
                                n_expand=n_expand, snapshot=snap,
                                active=valid,
                                record_heat=record_heat)
        heat_delta = _heat_delta(state, res) if record_heat \
            else jnp.zeros_like(state.heat)
        return res, heat_delta

    @jax.jit
    def _resolve(state):
        return lsm.snapshot_rows(cfg.lsm_cfg, state.store, cfg.cap)

    def _heat_delta(state, res):
        nodes = res.heat_nodes.reshape(-1)
        mask = res.heat_mask.reshape(-1, cfg.M)
        safe = jnp.maximum(nodes, 0)
        contrib = jnp.where((nodes >= 0)[:, None], mask, False)
        return jnp.zeros_like(state.heat).at[safe].add(
            contrib.astype(jnp.int32))

    return SimpleNamespace(
        insert=_insert, insert_batch=_insert_batch,
        insert_batch_snap=_insert_batch_snap, delete=_delete,
        delete_batch=_delete_batch, consolidate=_consolidate,
        consolidate_bg=_consolidate_bg, search=_search,
        search_snap=_search_snap, resolve=_resolve)


class DispatchedSearch:
    """`SearchHandle` over raw device arrays (DESIGN.md §13).

    Holds the jit outputs without forcing a host sync; `collect()` is
    the one blocking point (`np.asarray`) and slices the padded batch
    back to `[nq, k]` host-side.
    """

    __slots__ = ("_ids", "_dists", "_nq", "_k")

    def __init__(self, ids, dists, nq: int, k: int):
        self._ids, self._dists = ids, dists
        self._nq, self._k = nq, k

    def is_ready(self) -> bool:
        try:
            return bool(self._ids.is_ready() and self._dists.is_ready())
        except AttributeError:      # already a host array
            return True

    def collect(self) -> SearchResult:
        with declared_sync("search result materialization"):
            # sync-ok: collect() is the protocol's declared result sync point
            return SearchResult(
                ids=np.asarray(self._ids)[:self._nq, :self._k],
                dists=np.asarray(self._dists)[:self._nq, :self._k])


class LSMVecIndex:
    """Dynamic disk-based vector index (LSM-VEC).

    The single-device `VectorBackend` implementation (DESIGN.md §10):
    everything above the functional core programs against the protocol
    in `core/backend.py`, for which this class is the reference.
    """

    #: below this many live nodes, insert_batch falls back to per-item
    #: inserts: the batched pipeline searches the pre-batch graph snapshot,
    #: which must exist for the new nodes to link into (DESIGN.md §4)
    BATCH_MIN_GRAPH = 64

    def __init__(self, cfg: hnsw.HNSWConfig, seed: int = 0,
                 state: Optional[hnsw.HNSWState] = None):
        self.cfg = cfg
        self._seed = seed
        self.state = state if state is not None else hnsw.init(
            cfg, jax.random.key(seed))
        # commit the state to its device: committedness is part of the
        # jit executable cache key, and the overlapped-repair cutover
        # hands back a committed state — pinning up front means the
        # first repair never invalidates warmed-up executables
        self.state = jax.device_put(self.state, self._home_device())
        self._rng = jax.random.key(seed + 1)
        self.io_stats = IOStats.zero()
        # host mirror of state.count: id allocation and maintenance never
        # pay a device sync on the hot path
        self._count = int(self.state.count)
        # write-epoch counter + cached dense read snapshot (DESIGN.md §8):
        # every mutation bumps _version; the snapshot is lazily re-resolved
        # when a snapshot read observes a version mismatch
        self._version = 0
        self._snap = None
        self._snap_version = -1
        #: incremental snapshot patches applied (vs full re-resolves)
        self.snap_patches = 0
        # overlapped consolidation (DESIGN.md §13): (new_state, io, n)
        # while a double-buffered repair is in flight, plus the report of
        # the last repair finished by a write barrier, awaiting claim
        self._pending_repair = None
        self._done_report: Optional[MaintenanceReport] = None

        p = jit_programs(cfg)
        self._insert_fn = p.insert
        self._insert_batch_fn = p.insert_batch
        self._insert_batch_snap_fn = p.insert_batch_snap
        self._delete_fn = p.delete
        self._delete_batch_fn = p.delete_batch
        self._consolidate_fn = p.consolidate
        self._consolidate_bg_fn = p.consolidate_bg
        self._search_fn = p.search
        self._search_snap_fn = p.search_snap
        self._resolve_fn = p.resolve

    # -- construction ---------------------------------------------------------

    @classmethod
    def build(cls, cfg: hnsw.HNSWConfig, vectors: jax.Array,
              seed: int = 0) -> "LSMVecIndex":
        idx = cls(cfg, seed=seed, state=hnsw.bulk_build(
            cfg, jnp.asarray(vectors, jnp.float32), jax.random.key(seed)))
        return idx

    # -- updates --------------------------------------------------------------

    def _barrier_repair(self) -> None:
        """Write barrier: force-finish any in-flight overlapped repair.

        Every mutation calls this first, so a consolidation cutover
        always lands on a write-batch boundary — the invariant that
        makes WAL replay deterministic (DESIGN.md §13).  The finished
        report is stashed for the next `poll_maintain` to claim."""
        if self._pending_repair is not None:
            self._finish_repair()

    def _finish_repair(self) -> None:
        """Atomic cutover to the repaired state.  Edge heat recorded by
        queries that served *during* the repair is dropped with the old
        state: consolidate zeroes heat on every changed row anyway and
        heat is a purely advisory signal (tier/reorder triggers)."""
        new_state, st, n = self._pending_repair
        self._pending_repair = None
        self.state = new_state
        self.io_stats = self.io_stats + st
        self._version += 1
        self._done_report = MaintenanceReport(
            op="consolidate", applied=True, reclaimed=n,
            detail={"overlapped": True})

    def insert(self, x) -> int:
        """Insert one vector; returns its id."""
        self._barrier_repair()
        self._rng, sub = jax.random.split(self._rng)
        new_id = self._count
        self.state, st = self._insert_fn(
            self.state, jnp.asarray(x, jnp.float32), sub)
        self._count += 1
        self._version += 1
        self.io_stats = self.io_stats + st
        return new_id

    def insert_batch(self, xs, *,
                     pad_to: Optional[int] = None) -> UpdateResult:
        """Insert a batch in one jit'd device call; returns the new ids
        as an `UpdateResult`.

        The whole batch is dispatched as a single donated-buffer
        `hnsw.insert_batch` (vmapped candidate search + scanned writes)
        with zero per-item host syncs.  While the graph is smaller than
        BATCH_MIN_GRAPH the leading items fall back to per-item inserts so
        the batch pipeline always has a snapshot to search.

        `pad_to` is the fixed-shape dispatch hook (DESIGN.md §8): the
        batch is zero-padded to that width with a validity prefix mask, so
        every call reuses one traced shape regardless of how many items a
        serving micro-batch actually carries (batches larger than `pad_to`
        chunk).  Without it the jit specializes on the exact batch length.

        When the cached read snapshot is fresh, the batch routes through
        the overlay-returning variant and *patches* the snapshot in the
        same jit (one `jnp.where` over the staged write set) instead of
        invalidating it — the next query batch skips the full
        `lsm.resolve_all` re-resolve (DESIGN.md §13).
        """
        self._barrier_repair()
        xs = np.asarray(xs, np.float32)
        if xs.size == 0:
            return UpdateResult(ids=np.zeros((0,), np.int64), n_applied=0)
        xs = np.atleast_2d(xs)
        # guard on *live* size, not allocated ids: a graph emptied by
        # deletes must re-seed per-item too (one scalar sync per batch
        # call, never per item)
        n_seed = max(0, min(len(xs), self.BATCH_MIN_GRAPH - self.size))
        ids = [self.insert(x) for x in xs[:n_seed]]
        rest = xs[n_seed:]
        if len(rest) == 0:
            return UpdateResult(ids=np.asarray(ids, np.int64),
                                n_applied=len(ids))
        patch = self._snap is not None and self._snap_version == self._version
        width = pad_to if pad_to else len(rest)
        for s in range(0, len(rest), width):
            chunk = rest[s:s + width]
            n = len(chunk)
            padded = np.zeros((width, rest.shape[1]), np.float32)
            padded[:n] = chunk
            valid = np.arange(width) < n
            self._rng, sub = jax.random.split(self._rng)
            keys = jax.random.split(sub, width)
            ids.extend(range(self._count, self._count + n))
            if patch:
                self.state, st, self._snap = self._insert_batch_snap_fn(
                    self.state, jnp.asarray(padded), keys,
                    jnp.asarray(valid), self._snap)
                self.snap_patches += 1
            else:
                self.state, st = self._insert_batch_fn(
                    self.state, jnp.asarray(padded), keys, jnp.asarray(valid))
            self._count += n
            self._version += 1
            if patch:
                self._snap_version = self._version
            self.io_stats = self.io_stats + st
        return UpdateResult(ids=np.asarray(ids, np.int64),
                            n_applied=len(ids))

    def delete(self, node_id: int) -> None:
        """Delete one id.  Under `cfg.lazy_delete` (default) this only
        sets the tombstone bit — no LSM write, so the cached read
        snapshot stays valid (the returnable mask, not the snapshot,
        hides the node)."""
        self._barrier_repair()
        self.state, st = self._delete_fn(self.state, jnp.asarray(node_id))
        if not self.cfg.lazy_delete:
            self._version += 1
        self.io_stats = self.io_stats + st

    def delete_batch(self, ids, *,
                     pad_to: Optional[int] = None) -> UpdateResult:
        """Delete a batch of ids in one jit'd device call.

        `pad_to` pads the id vector with -1 (masked no-ops in
        `hnsw.delete_batch`) so serving micro-batches of any occupancy
        dispatch through one traced shape; larger batches chunk.  Lazy
        deletes leave the read snapshot valid (tombstone-bit only).
        """
        self._barrier_repair()
        ids = np.atleast_1d(np.asarray(ids, np.int32))
        if len(ids) == 0:
            return UpdateResult(ids=np.zeros((0,), np.int64), n_applied=0)
        width = pad_to or len(ids)
        for s in range(0, len(ids), width):
            chunk = ids[s:s + width]
            padded = np.full((width,), -1, np.int32)
            padded[:len(chunk)] = chunk
            self.state, st = self._delete_batch_fn(
                self.state, jnp.asarray(padded))
            if not self.cfg.lazy_delete:
                self._version += 1
            self.io_stats = self.io_stats + st
        return UpdateResult(ids=ids.astype(np.int64),
                            n_applied=int((ids >= 0).sum()))

    # -- search ---------------------------------------------------------------

    def dispatch_search(self, queries, k: Optional[int] = None, *,
                        params: Optional[SearchParams] = None
                        ) -> DispatchedSearch:
        """Enqueue a batched ANN search; no host sync (DESIGN.md §13).

        queries [B, dim] -> `DispatchedSearch` whose `collect()` blocks
        on the device arrays and returns the final `SearchResult`
        (ids [B, k], dists [B, k]).  All knobs ride in `params`
        (`SearchParams`); `None` fields resolve from the config here —
        the single defaults site.

        `params.n_expand` > 1 expands that many frontier nodes per beam
        iteration (multi-expansion); 1 is the classic exact-parity path.
        `params.use_snapshot` serves bottom-layer adjacency from the
        cached dense LSM view (`snapshot()`), re-resolved (or overlay-
        patched) only after writes — identical results, but each hop is
        a row gather instead of an LSM probe.  `params.pad_to` zero-pads
        the query batch to a fixed width with masked lanes so every call
        shares one traced shape (implies the snapshot path, which is
        where the mask-aware kernels live).
        """
        p = (params or SearchParams()).resolve(self.cfg)
        k = k or self.cfg.k
        qs_np = np.atleast_2d(np.asarray(queries, np.float32))
        nq = len(qs_np)
        if p.use_snapshot or p.pad_to is not None:
            width = p.pad_to if p.pad_to else nq
            if nq > width:
                raise ValueError(f"batch {nq} exceeds pad width {width}")
            padded = np.zeros((width, qs_np.shape[1]), np.float32)
            padded[:nq] = qs_np
            valid = np.arange(width) < nq
            res, heat_delta = self._search_snap_fn(
                self.state, jnp.asarray(padded), jnp.asarray(valid),
                self.snapshot(), p.rho, p.use_filter, p.ef, p.n_expand,
                p.record_heat)
        else:
            res, heat_delta = self._search_fn(
                self.state, jnp.asarray(qs_np), p.rho, p.use_filter,
                p.ef, p.n_expand, p.record_heat)
        if p.record_heat:
            self.state = self.state._replace(
                heat=self.state.heat + heat_delta)
        batch_stats = jax.tree.map(lambda a: jnp.sum(a), res.stats)
        self.io_stats = self.io_stats + IOStats(*batch_stats)
        # slicing happens host-side at collect(): device slicing would
        # re-specialize on every distinct residual batch length
        return DispatchedSearch(res.ids, res.dists, nq, k)

    def search(self, queries, k: Optional[int] = None, *,
               params: Optional[SearchParams] = None) -> SearchResult:
        """Batched ANN search: dispatch + collect in one call."""
        return self.dispatch_search(queries, k, params=params).collect()

    # -- maintenance ----------------------------------------------------------

    def maintain(self, op: str, **params) -> MaintenanceReport:
        """Uniform maintenance entry point (`VectorBackend` protocol).

        ops: "consolidate" (`ratio=`), "compact", "reorder"
        (`window=`, `lam=`), "tier" (`policy=`).  The legacy per-op
        methods remain as thin deprecated wrappers around the same
        implementations.
        """
        if op == "consolidate":
            # a repair finished by a write barrier (or still in flight)
            # IS this consolidation — claim it instead of re-running
            rep = self.poll_maintain(block=True)
            if rep is not None and rep.applied:
                return rep
            n = self.consolidate(ratio=params.get("ratio"))
            return MaintenanceReport(op=op, applied=n > 0, reclaimed=n)
        if op == "compact":
            self.compact()
            return MaintenanceReport(op=op, applied=True)
        if op == "reorder":
            perm = self.reorder(window=int(params.get("window", 8)),
                                lam=float(params.get("lam", 1.0)))
            return MaintenanceReport(op=op, applied=True, perm=perm)
        if op == "tier":
            moved = self.tier_maintain(params["policy"])
            return MaintenanceReport(
                op=op, applied=(moved["demoted"] + moved["promoted"]) > 0,
                demoted=moved["demoted"], promoted=moved["promoted"])
        raise ValueError(f"unknown maintenance op {op!r}")

    def begin_maintain(self, op: str, **params) -> bool:
        """Start an overlapped consolidation (DESIGN.md §13).

        Runs the `lax.map` splice repair against a *non-donated* clone
        of the live state: queries keep dispatching on `self.state`
        while the repair computes.  Returns True iff a repair was
        started (False: unsupported op, one already in flight, or the
        tombstone-ratio trigger declined).  Cutover happens in
        `poll_maintain` — or earlier, at the next mutation's write
        barrier.
        """
        if op != "consolidate" or self._pending_repair is not None:
            return False
        with declared_sync("maintenance cadence scalar"):
            # sync-ok: scalar sync up front — maintenance cadence, not hot path
            n = int(self.state.n_tombstones)
        if n == 0:
            return False
        ratio = params.get("ratio")
        if ratio is not None and n / max(self.size + n, 1) < ratio:
            return False
        spare = self._spare_device()
        if spare is not None:
            # run the repair on a spare device so it never serializes
            # the serving device's execution stream: queries dispatched
            # during the repair start immediately instead of queueing
            # behind a cap-sized rebuild.  The repaired state rides a
            # device-to-device transfer home, enqueued behind the
            # compute — cutover still just swaps the pointer.
            src = jax.device_put(self.state, spare)
            out = self._consolidate_bg_fn(src)
            new_state, st = jax.device_put(out, self._home_device())
        else:
            new_state, st = self._consolidate_bg_fn(self.state)
        self._pending_repair = (new_state, st, n)
        return True

    def _home_device(self):
        """The device the live state is committed to."""
        return next(iter(self.state.count.devices()))

    def _spare_device(self):
        """A local device other than the home device, if one exists —
        where overlapped repairs run (DESIGN.md §13).  Deterministic
        (next device in the local ring) so the repair executable
        compiles exactly once per index."""
        devs = jax.local_devices()
        if len(devs) < 2:
            return None
        home = self._home_device()
        try:
            i = devs.index(home)
        except ValueError:
            return None
        return devs[(i + 1) % len(devs)]

    def poll_maintain(self, *, block: bool = False
                      ) -> Optional[MaintenanceReport]:
        """Cut over to a finished repair and return its report.

        Non-blocking by default: returns None while the repair's device
        work is still running (polled via `jax.Array.is_ready`).  Also
        returns (and clears) the report of a repair that a write
        barrier already finished.  `block=True` forces the cutover.
        """
        if self._pending_repair is not None:
            new_state = self._pending_repair[0]
            ready = getattr(new_state.count, "is_ready", lambda: True)()
            if not (block or ready):
                return None
            self._finish_repair()
        rep, self._done_report = self._done_report, None
        return rep

    @property
    def maintenance_pending(self) -> bool:
        """A repair is in flight or a finished report awaits claim."""
        return (self._pending_repair is not None
                or self._done_report is not None)

    def reorder(self, *, window: int = 8, lam: float = 1.0) -> np.ndarray:
        """Connectivity-aware relayout (§3.4), applied at compaction.
        Deprecated entry point — prefer `maintain("reorder", ...)`."""
        self._barrier_repair()
        n = self._count
        live, rows = lsm.resolve_all(self.cfg.lsm_cfg, self.state.store, n)
        with declared_sync("reorder host relayout"):
            # sync-ok: gorder relayout is a host-side maintenance pass
            live_np = np.asarray(live).astype(bool) & (
                np.asarray(self.state.levels[:n]) >= 0)
            # sync-ok: gorder relayout is a host-side maintenance pass
            perm = reorder.gorder_permutation(
                np.asarray(rows), np.asarray(self.state.heat[:n]),
                window=window, lam=lam, live=live_np)
        self.state = reorder.apply_permutation(self.cfg, self.state, perm)
        self._version += 1
        return perm

    def compact(self) -> None:
        """Deprecated entry point — prefer `maintain("compact")`."""
        self._barrier_repair()
        self.state = self.state._replace(
            store=lsm.compact_all(self.cfg.lsm_cfg, self.state.store))
        self._version += 1

    def consolidate(self, *, ratio: Optional[float] = None) -> int:
        """Splice tombstoned nodes out of the graph and reclaim slots
        (lazy-deletion phase 2, DESIGN.md §9).  Returns the number of
        slots reclaimed.  `ratio` applies the per-shard trigger rule of
        the backend protocol: skip unless tombstones / (live +
        tombstones) has reached it (None = unconditional).  Internal ids
        are never reused, so external id maps stay valid with no
        rewrite.  One scalar sync up front — this is the rare
        maintenance path, not the serving hot path.  Deprecated entry
        point — prefer `maintain("consolidate", ratio=...)` or the
        overlapped `begin_maintain`/`poll_maintain` pair."""
        self._barrier_repair()
        with declared_sync("maintenance cadence scalar"):
            n = int(self.state.n_tombstones)  # sync-ok: maintenance cadence
        if n == 0:
            return 0
        if ratio is not None and n / max(self.size + n, 1) < ratio:
            return 0
        self.state, st = self._consolidate_fn(self.state)
        self.io_stats = self.io_stats + st
        self._version += 1
        return n

    def tier_maintain(self, policy: "tier_policy.TierPolicy") -> dict:
        """One batched demote/promote pass of the tier policy
        (DESIGN.md §12).  Returns {"demoted": n, "promoted": n}.  Jit
        caches key on (cfg, policy), both static — a serving layer using
        one policy compiles this exactly once.  No-op (zero moves) when
        the hot fraction already sits inside the hysteresis band.
        Deprecated entry point — prefer `maintain("tier", policy=...)`.
        """
        self._barrier_repair()
        self.state, st, moved = tier_policy.tier_maintain(
            self.cfg, self.state, policy)
        self.io_stats = self.io_stats + st
        return {k: int(v) for k, v in moved.items()}

    # -- read snapshot (DESIGN.md §8) -----------------------------------------

    def snapshot(self) -> jax.Array:
        """Dense bottom-layer adjacency view int32[cap, M], cached.

        Resolved lazily from the LSM tree and reused across consecutive
        query batches; any write (insert/delete/compact/reorder) bumps the
        index version and the next call re-resolves.
        """
        if self._snap is None or self._snap_version != self._version:
            self._snap = self._resolve_fn(self.state)
            self._snap_version = self._version
        return self._snap

    # -- backend protocol surface (DESIGN.md §10) -----------------------------

    @property
    def cap(self) -> int:
        """Total internal id space (the `VectorBackend` contract)."""
        return self.cfg.cap

    @property
    def lazy_delete(self) -> bool:
        return self.cfg.lazy_delete

    @property
    def snapshot_stale(self) -> bool:
        """True when the next snapshot read will re-resolve the tree."""
        return self._snap is None or self._snap_version != self._version

    def stats(self) -> BackendStats:
        """The backend stats surface — one fused device fetch.

        This is the single accessor for the device-side delete no-op
        count (the old `LSMVecIndex.delete_noops` / engine-property pair
        could drift); serving metrics must read it from here.
        """
        with declared_sync("stats surface fetch"):
            # sync-ok: the single fused device fetch of the stats surface
            live, nt, noops, counts = jax.device_get(
                (self.state.n_live, self.state.n_tombstones,
                 self.state.n_delete_noops, hnsw.memory_counts(self.state)))
        live, nt, noops = int(live), int(nt), int(noops)
        mem = hnsw.memory_breakdown(self.cfg, self.state, counts)
        shard = ShardStats(size=live, n_tombstones=nt, delete_noops=noops,
                           n_hot=mem.n_hot, n_cold=mem.n_cold)
        return BackendStats(size=live, n_tombstones=nt, delete_noops=noops,
                            max_tombstone_ratio=shard.tombstone_ratio,
                            shards=(shard,), memory=mem)

    def heat_total(self) -> int:
        """Accumulated edge-heat counts (one scalar sync)."""
        with declared_sync("heat trigger scalar"):
            return int(jnp.sum(self.state.heat))  # sync-ok: heat cadence

    def initial_ids(self) -> np.ndarray:
        """Internal ids in allocation order, for seeding an external-id
        map: the j-th vector ever allocated holds internal id j."""
        return np.arange(self._count, dtype=np.int64)

    def sync(self) -> None:
        with declared_sync("explicit barrier"):
            # sync-ok: sync() is the protocol's explicit barrier API
            jax.block_until_ready(self.state.count)

    def clone(self) -> "LSMVecIndex":
        """Deep-copy the device state into a fresh index (fresh jit
        caches too — benchmark trials use this to undo donation).  The
        RNG stream carries over, so a clone inserts with the same
        randomness the original would have."""
        self._barrier_repair()
        other = LSMVecIndex(self.cfg, seed=self._seed,
                            state=jax.tree.map(jnp.copy, self.state))
        other._rng = self._rng
        return other

    # -- durability (DESIGN.md §11) -------------------------------------------

    def save(self, ckpt_dir: str, *, lsn: int = 0,
             extra: Optional[dict] = None, meta: Optional[dict] = None,
             keep: int = 3, _pre_publish=None) -> str:
        """Atomic full-state checkpoint (`VectorBackend` protocol).

        Everything needed for bit-exact resume goes in: the complete
        `HNSWState` (vectors, codes, upper layers, LSM store, tombstone
        lane, heat), the insert RNG stream (so replayed inserts draw the
        same level/edge randomness), and caller `extra` arrays (the
        serve engine's ext↔int map and deleted mask).  `lsn` is the
        covering WAL position — recovery replays only records after it —
        and doubles as the checkpoint step, so steps are monotone as
        long as the caller only checkpoints after new writes.
        """
        self._barrier_repair()
        self.sync()
        tree = lsm.dehydrate(self.state, "state")
        tree["rng"] = jax.random.key_data(self._rng)
        for k, v in (extra or {}).items():
            tree[f"extra/{k}"] = np.asarray(v)
        metadata = {"lsn": int(lsn), "count": self._count,
                    "version": self._version, "seed": self._seed,
                    "cap": self.cfg.cap, "dim": self.cfg.dim,
                    **(meta or {})}
        return ckpt.save_checkpoint(ckpt_dir, step=int(lsn), tree=tree,
                                    metadata=metadata, keep=keep,
                                    _pre_publish=_pre_publish)

    @classmethod
    def restore(cls, cfg: hnsw.HNSWConfig, ckpt_dir: str, *,
                step: Optional[int] = None
                ) -> Tuple["LSMVecIndex", dict, dict]:
        """Rebuild an index from its latest (or `step`-th) checkpoint.

        Structure comes from `cfg` (shapes are config-derived), values
        from the manifest; every config-required leaf must be present
        with the exact shape or the restore refuses — a checkpoint from
        a different cap/dim/M must never load silently.  Returns
        (index, metadata, extras) where extras are the caller arrays
        passed to `save(extra=...)`, keys unprefixed.
        """
        arrays, metadata, _ = ckpt.load_arrays(ckpt_dir, step)
        if (int(metadata["cap"]) != cfg.cap
                or int(metadata["dim"]) != cfg.dim):
            raise ValueError(
                f"checkpoint cap/dim ({metadata['cap']}/{metadata['dim']}) "
                f"!= config ({cfg.cap}/{cfg.dim})")
        seed = int(metadata.get("seed", 0))
        template = hnsw.init(cfg, jax.random.key(seed))
        leaves = {}
        for k, tmpl in lsm.dehydrate(template, "state").items():
            if k not in arrays:
                raise KeyError(f"checkpoint missing state leaf {k!r}")
            arr = arrays[k]
            if tuple(arr.shape) != tuple(tmpl.shape):
                raise ValueError(
                    f"{k}: checkpoint shape {tuple(arr.shape)} != "
                    f"config-derived {tuple(tmpl.shape)}")
            leaves[k] = jnp.asarray(arr, tmpl.dtype)
        state = lsm.hydrate(template, leaves, "state")
        idx = cls(cfg, seed=seed, state=state)
        idx._rng = jax.random.wrap_key_data(jnp.asarray(arrays["rng"]))
        idx._count = int(metadata["count"])
        idx._version = int(metadata["version"])
        extras = {k[len("extra/"):]: v for k, v in arrays.items()
                  if k.startswith("extra/")}
        return idx, metadata, extras

    # -- accounting -----------------------------------------------------------

    def reset_stats(self) -> None:
        self.io_stats = IOStats.zero()

    def reset_heat(self) -> None:
        """Zero the edge-heat accumulator (after a heat-driven relayout)."""
        self._barrier_repair()
        self.state = self.state._replace(heat=jnp.zeros_like(self.state.heat))

    def trace_counts(self) -> dict:
        """Compiled-variant counts per jitted entry point.

        The serving layer's zero-retrace guarantee is asserted against
        these: with fixed pad widths each op converges to a constant
        number of traced shapes after warmup.
        """
        return {
            "insert": self._insert_fn._cache_size(),
            "insert_batch": self._insert_batch_fn._cache_size(),
            "insert_batch_snapshot": self._insert_batch_snap_fn._cache_size(),
            "delete": self._delete_fn._cache_size(),
            "delete_batch": self._delete_batch_fn._cache_size(),
            "search": self._search_fn._cache_size(),
            "search_snapshot": self._search_snap_fn._cache_size(),
            "consolidate_bg": self._consolidate_bg_fn._cache_size(),
        }

    def io_cost(self, model: CostModel = iostats.DISK) -> float:
        return float(iostats.search_cost(self.io_stats, model))

    def memory_breakdown(self) -> MemoryBreakdown:
        """Per-component resident bytes (DESIGN.md §12)."""
        return hnsw.memory_breakdown(self.cfg, self.state)

    def memory_bytes(self) -> int:
        with declared_sync("memory accounting scalar"):
            return int(self.memory_breakdown().total)

    @property
    def size(self) -> int:
        with declared_sync("live-count scalar"):
            return int(self.state.n_live)  # sync-ok: declared accessor

    @property
    def n_tombstones(self) -> int:
        """Nodes lazily deleted but not yet consolidated (one sync)."""
        with declared_sync("tombstone-count scalar"):
            return int(self.state.n_tombstones)  # sync-ok: declared accessor
