"""Mesh-sharded vector search: partition-per-device serving.

The paper's billion-scale deployment note (§5.1) — "billion-scale indices
are typically partitioned or sharded in real-world systems" — is realized
here: the corpus is split into P shards, each device owns one shard's
index state, a query fans out to every shard, local top-k results come
back per shard, and a global top-k merge produces the answer.  Recall of
the merged result equals single-shard recall because every shard is
searched (SPANN-style partition serving).

Two shard-local engines:
 - "flat": exact blocked L2 scan (the memory-bandwidth-optimal TPU form);
 - `ShardedBackend`: P full `LSMVecIndex` shards behind the
   `VectorBackend` protocol (DESIGN.md §10) — hash-partitioned routing,
   per-shard updates/tombstones/consolidation, fan-out search.
"""

from __future__ import annotations

import json
import os
import shutil
from concurrent.futures import ThreadPoolExecutor
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.checkpoint import ckpt
from repro.core import hnsw
from repro.core.backend import (
    BackendStats,
    MaintenanceReport,
    SearchParams,
    SearchResult,
    UpdateResult,
    merge_topk,
    shard_of_seq,
)
from repro.core.index import LSMVecIndex
from repro.kernels.l2_distance.ref import l2_distance_ref


class ShardedFlatIndex:
    """Exact partitioned search over a device mesh axis."""

    def __init__(self, mesh, axis: str = "data"):
        self.mesh = mesh
        self.axis = axis
        self.vectors = None           # [P, n_per, d] sharded on axis 0
        self.n_per = 0

    def build(self, vectors: np.ndarray) -> "ShardedFlatIndex":
        p = self.mesh.devices.size
        n, d = vectors.shape
        n_per = -(-n // p)
        pad = n_per * p - n
        vecs = np.pad(vectors, ((0, pad), (0, 0)),
                      constant_values=np.inf).astype(np.float32)
        # inf-padding keeps padded rows out of every top-k
        arr = jnp.asarray(vecs.reshape(p, n_per, d))
        sharding = jax.sharding.NamedSharding(
            self.mesh, P(tuple(self.mesh.axis_names)))
        self.vectors = jax.device_put(arr, sharding)
        self.n_per = n_per
        self._search = self._make_search()
        return self

    def _make_search(self):
        mesh = self.mesh
        axes = tuple(mesh.axis_names)
        n_per = self.n_per
        p = mesh.devices.size

        def local(shard_id, vecs, queries):
            # shard_id [1] (this shard's slot — order-correct by
            # construction), vecs [1, n_per, d], queries [Q, d] replicated
            d2 = l2_distance_ref(queries, vecs[0])          # [Q, n_per]
            d2 = jnp.where(jnp.isfinite(d2), d2, jnp.inf)
            k = min(16, n_per)
            neg, idx = jax.lax.top_k(-d2, k)
            gids = idx + shard_id[0] * n_per                # global ids
            # gather every shard's candidates, merge
            all_d = jax.lax.all_gather(-neg, axes, tiled=False)
            all_i = jax.lax.all_gather(gids, axes, tiled=False)
            all_d = all_d.reshape(-1, *neg.shape)
            all_i = all_i.reshape(-1, *gids.shape)
            all_d = jnp.swapaxes(all_d, 0, 1).reshape(queries.shape[0], -1)
            all_i = jnp.swapaxes(all_i, 0, 1).reshape(queries.shape[0], -1)
            negd, pos = jax.lax.top_k(-all_d, 10)
            return jnp.take_along_axis(all_i, pos, axis=1), -negd

        fn = jax.shard_map(
            local, mesh=mesh,
            in_specs=(P(axes), P(axes), P()), out_specs=(P(), P()),
            check_vma=False)
        self._shard_ids = jax.device_put(
            jnp.arange(p, dtype=jnp.int32),
            jax.sharding.NamedSharding(self.mesh, P(axes)))
        return jax.jit(fn)

    def search(self, queries, k: int = 10) -> Tuple[np.ndarray, np.ndarray]:
        ids, dists = self._search(self._shard_ids, self.vectors,
                                  jnp.asarray(queries, jnp.float32))
        return np.asarray(ids)[:, :k], np.asarray(dists)[:, :k]


class ShardedDispatch:
    """`SearchHandle` over the per-shard in-flight handles.

    Dispatch already happened (all shards' device work is enqueued);
    `collect()` blocks shard by shard, maps local ids into the
    block-encoded global space, and runs the stable `merge_topk` host
    merge.  Total wait is the *max* shard latency, not the sum — the
    overlap the two-phase contract buys (DESIGN.md §13).
    """

    __slots__ = ("_handles", "_cap", "_k")

    def __init__(self, handles, cap: int, k: int):
        self._handles = handles
        self._cap = cap
        self._k = k

    def is_ready(self) -> bool:
        return all(h.is_ready() for h in self._handles)

    def collect(self) -> SearchResult:
        gids, dists = [], []
        for s, h in enumerate(self._handles):
            res = h.collect()
            base = np.int64(s) * self._cap
            gids.append(np.where(res.ids >= 0,
                                 res.ids.astype(np.int64) + base, -1))
            dists.append(res.dists)
        return merge_topk(gids, dists, self._k)


def _each_shard(fn, items) -> list:
    """`[fn(x) for x in items]`, one thread per item.  Shards are
    independent, and a shard's first call of a program compiles it for
    that shard's own device: run one after another, a four-chip host
    pays every compile four times over; concurrently, the compiles and
    each shard's host work overlap (JAX releases the GIL while it
    compiles and while devices run)."""
    items = list(items)
    if len(items) <= 1:
        return [fn(x) for x in items]
    with ThreadPoolExecutor(len(items)) as pool:
        return list(pool.map(fn, items))


class ShardedBackend:
    """P independent LSM-VEC shards behind one `VectorBackend` surface.

    Promotes the old build+search-only `ShardedLSMVec` into a full
    backend (DESIGN.md §10): every shard is a complete `LSMVecIndex`
    (insert/delete/lazy-delete/consolidate/compact/reorder), committed
    round-robin to the available devices, and the class owns only
    routing and merging:

    - **id space** — block-encoded global ids: shard s's local id l is
      global id ``s * cfg.cap + l``.  With one shard the encoding is
      the identity, which is what makes shards=1 bit-parity with a bare
      `LSMVecIndex` (the acceptance anchor for the serve layer).
    - **routing** — a new vector goes to shard
      ``hash(allocation_seq) % P`` (`shard_of_seq`): deterministic,
      load-balanced, content-independent.  Deletes/reorders route by
      the shard block encoded in the id.
    - **search** — fan out the query batch to every shard; each shard
      computes its local top-k on device; the host merge
      (`merge_topk`) is a stable P-way merge of the distance-sorted
      rows.
    - **maintenance** — per-shard triggers: `consolidate(ratio=r)`
      consolidates exactly the shards whose own tombstone ratio
      reached r; `reorder` composes per-shard permutations into one
      global permutation for the serving layer's id map.
    """

    def __init__(self, cfg: hnsw.HNSWConfig, n_shards: int, *,
                 devices: Optional[Sequence] = None, seed: int = 0):
        self.cfg = cfg
        self.n_shards = n_shards
        self.seed = seed
        if devices is None:
            devices = jax.local_devices()
        self.devices = [devices[s % len(devices)] for s in range(n_shards)]
        # shard states are expensive (full cap-sized arrays per shard):
        # materialize lazily so build()/clone(), which install their own
        # shards, never pay for throwaway empties
        self._shards: Optional[list] = None
        self._n_routed = 0           # global allocation counter (routing)
        self._alloc: list[int] = []  # global ids in allocation order
        self.consolidations = [0] * n_shards   # per-shard maintenance log
        # overlapped consolidation: per-shard reports already claimed
        # while other shards' repairs are still in flight
        self._claimed: dict = {}

    def _empty_shard(self, s: int) -> LSMVecIndex:
        return LSMVecIndex(
            self.cfg, seed=self.seed + s,
            state=jax.device_put(
                hnsw.init(self.cfg, jax.random.key(self.seed + s)),
                self.devices[s]))

    @property
    def shards(self) -> list:
        if self._shards is None:
            self._shards = [self._empty_shard(s)
                            for s in range(self.n_shards)]
        return self._shards

    # -- construction ---------------------------------------------------------

    def build(self, vectors: np.ndarray, seed: int = 0) -> "ShardedBackend":
        """Bulk-build the shards from `vectors`, routed like a stream.

        Row j routes to `shard_of_seq(j)` — the same rule later inserts
        follow — so a build is indistinguishable from inserting the
        rows one by one.  `initial_ids()` returns the global id of each
        row in build order for seeding an external-id map.
        """
        n = len(vectors)
        vectors = np.asarray(vectors, np.float32)
        self.seed = seed
        asg = np.asarray(shard_of_seq(np.arange(n), self.n_shards))

        def build_shard(s: int) -> LSMVecIndex:
            rows = np.flatnonzero(asg == s)
            if len(rows) == 0:
                return self._empty_shard(s)
            with jax.default_device(self.devices[s]):
                st = hnsw.bulk_build(self.cfg, jnp.asarray(vectors[rows]),
                                     jax.random.key(seed + s))
            return LSMVecIndex(self.cfg, seed=seed + s,
                               state=jax.device_put(st, self.devices[s]))

        self._shards = _each_shard(build_shard, range(self.n_shards))
        local = np.zeros(n, np.int64)
        for s in range(self.n_shards):
            rows = np.flatnonzero(asg == s)
            local[rows] = np.arange(len(rows))
        self._alloc = (asg.astype(np.int64) * self.cfg.cap + local).tolist()
        self._n_routed = n
        return self

    # -- backend protocol -----------------------------------------------------

    @property
    def cap(self) -> int:
        return self.n_shards * self.cfg.cap

    @property
    def lazy_delete(self) -> bool:
        return self.cfg.lazy_delete

    @property
    def snapshot_stale(self) -> bool:
        return any(sh.snapshot_stale for sh in self.shards)

    def _split(self, gid: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
        """Global id [N] -> (shard [N], local id [N]); -1 passes through."""
        gid = np.asarray(gid, np.int64)
        shard = np.where(gid >= 0, gid // self.cfg.cap, -1)
        local = np.where(gid >= 0, gid % self.cfg.cap, -1)
        return shard, local

    def dispatch_search(self, queries, k: Optional[int] = None, *,
                        params: Optional[SearchParams] = None
                        ) -> ShardedDispatch:
        """Two-phase fan-out (DESIGN.md §13): enqueue every shard's
        device-side local top-k *before* blocking on any result — the
        per-shard devices compute concurrently and `collect()` pays the
        max shard latency instead of the sum.  All per-query knobs
        forward to the shards unchanged, so the merged result at
        shards=1 is bit-identical to the single-device index."""
        k = k or self.cfg.k
        handles = [sh.dispatch_search(queries, k=k, params=params)
                   for sh in self.shards]
        return ShardedDispatch(handles, self.cfg.cap, k)

    def search(self, queries, k: Optional[int] = None, *,
               params: Optional[SearchParams] = None) -> SearchResult:
        """Fan-out search: dispatch to every shard, then the stable
        `merge_topk` host merge."""
        return self.dispatch_search(queries, k, params=params).collect()

    def insert_batch(self, xs, *,
                     pad_to: Optional[int] = None) -> UpdateResult:
        """Route each vector by its allocation sequence number, insert
        per shard in one padded device call each, and return the global
        ids in submission order."""
        xs = np.atleast_2d(np.asarray(xs, np.float32))
        if xs.size == 0:
            return UpdateResult(ids=np.zeros((0,), np.int64), n_applied=0)
        n = len(xs)
        asg = np.asarray(shard_of_seq(
            np.arange(self._n_routed, self._n_routed + n), self.n_shards))
        self._n_routed += n
        gids = np.full(n, -1, np.int64)
        routed = [(s, np.flatnonzero(asg == s)) for s in range(self.n_shards)]
        routed = [(s, rows) for s, rows in routed if len(rows)]
        results = _each_shard(
            lambda sr: self.shards[sr[0]].insert_batch(xs[sr[1]],
                                                       pad_to=pad_to),
            routed)
        for (s, rows), res in zip(routed, results):
            gids[rows] = np.asarray(res.ids, np.int64) \
                + np.int64(s) * self.cfg.cap
        # allocation order = submission order (ids are assigned in the
        # order each shard's sub-batch preserves); one batched host
        # conversion, not one numpy-scalar unboxing per id
        self._alloc.extend(gids.tolist())
        return UpdateResult(ids=gids, n_applied=n)

    def delete_batch(self, ids, *,
                     pad_to: Optional[int] = None) -> UpdateResult:
        """Route global ids to their owning shard blocks; negative or
        out-of-range ids are masked no-ops (the pad-and-mask serving
        contract) and are excluded from `n_applied`."""
        ids = np.atleast_1d(np.asarray(ids, np.int64))
        if len(ids) == 0:
            return UpdateResult(ids=ids, n_applied=0)
        shard, local = self._split(ids)
        routable = (shard >= 0) & (shard < self.n_shards)
        routed = [(s, local[shard == s]) for s in range(self.n_shards)]
        _each_shard(lambda sr: self.shards[sr[0]].delete_batch(
            sr[1].astype(np.int32), pad_to=pad_to),
            [(s, sub) for s, sub in routed if len(sub)])
        return UpdateResult(ids=ids, n_applied=int(routable.sum()))

    def maintain(self, op: str, **params) -> MaintenanceReport:
        """Uniform maintenance over all shards (`VectorBackend`
        protocol).  Per-shard reports aggregate componentwise; for
        "reorder" the per-shard permutations compose into one global
        permutation exactly as the legacy method did."""
        if op == "consolidate":
            # overlapped repairs still in flight ARE this consolidation:
            # claim them, then run the sync trigger on the rest
            pre = self.poll_maintain(block=True)
            total = pre.reclaimed if pre is not None else 0
            total += self.consolidate(ratio=params.get("ratio"))
            return MaintenanceReport(op=op, applied=total > 0,
                                     reclaimed=total)
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
        """Start overlapped consolidation on every shard whose own
        tombstone-ratio trigger passes (each repair runs on the shard's
        spare device — the next shard's home — concurrent with fan-out
        queries).  True iff at least one shard started."""
        if op != "consolidate":
            return False
        return any(_each_shard(lambda sh: sh.begin_maintain(op, **params),
                               self.shards))

    def poll_maintain(self, *, block: bool = False
                      ) -> Optional[MaintenanceReport]:
        """Claim finished per-shard repairs; once no shard repair is
        left in flight, return the aggregated report (None while any is
        still running, or when nothing was pending at all)."""
        for s, sh in enumerate(self.shards):
            rep = sh.poll_maintain(block=block)
            if rep is not None and rep.applied:
                self.consolidations[s] += 1
                self._claimed[s] = rep
        if any(sh.maintenance_pending for sh in self.shards):
            return None
        if not self._claimed:
            return None
        claimed, self._claimed = self._claimed, {}
        return MaintenanceReport(
            op="consolidate", applied=True,
            reclaimed=sum(r.reclaimed for r in claimed.values()),
            detail={"overlapped": True, "shards": sorted(claimed)})

    @property
    def maintenance_pending(self) -> bool:
        """A repair is in flight or a finished report awaits claim."""
        return bool(self._claimed) or any(sh.maintenance_pending
                                          for sh in self.shards)

    def consolidate(self, *, ratio: Optional[float] = None) -> int:
        """Per-shard trigger rule: each shard consolidates iff its own
        tombstone ratio reached `ratio` (None = every shard with any
        tombstones).  Returns total slots reclaimed.  Deprecated entry
        point — prefer `maintain("consolidate", ratio=...)`."""
        total = 0
        for s, sh in enumerate(self.shards):
            got = sh.consolidate(ratio=ratio)
            if got:
                self.consolidations[s] += 1
            total += got
        return total

    def compact(self) -> None:
        for sh in self.shards:
            sh.compact()

    def reorder(self, *, window: int = 8, lam: float = 1.0) -> np.ndarray:
        """Per-shard relayout composed into one global permutation
        (identity outside the permuted per-shard prefixes), so the
        serving layer folds it into its id map exactly like the
        single-device case."""
        perm = np.arange(self.cap, dtype=np.int64)
        for s, sh in enumerate(self.shards):
            ps = np.asarray(sh.reorder(window=window, lam=lam), np.int64)
            base = np.int64(s) * self.cfg.cap
            perm[base:base + len(ps)] = base + ps
        return perm

    def stats(self) -> BackendStats:
        full = [sh.stats() for sh in self.shards]
        per = tuple(f.shards[0] for f in full)
        mem = full[0].memory
        for f in full[1:]:
            mem = mem + f.memory
        return BackendStats(
            size=sum(p.size for p in per),
            n_tombstones=sum(p.n_tombstones for p in per),
            delete_noops=sum(p.delete_noops for p in per),
            max_tombstone_ratio=max(p.tombstone_ratio for p in per),
            shards=per, memory=mem)

    def tier_maintain(self, policy) -> dict:
        """Run the tier policy on every shard (each shard holds its own
        hot budget — heat is shard-local, like the consolidate trigger).
        Returns total moves across shards."""
        moved = {"demoted": 0, "promoted": 0}
        for sh in self.shards:
            got = sh.tier_maintain(policy)
            for k in moved:
                moved[k] += got[k]
        return moved

    def heat_total(self) -> int:
        return sum(sh.heat_total() for sh in self.shards)

    def reset_heat(self) -> None:
        for sh in self.shards:
            sh.reset_heat()

    def initial_ids(self) -> np.ndarray:
        return np.asarray(self._alloc, np.int64)

    def trace_counts(self) -> dict:
        """Compiled-variant counts summed across shards (the serve
        zero-retrace proof compares totals before/after load)."""
        out: dict = {}
        for sh in self.shards:
            for key, v in sh.trace_counts().items():
                out[key] = out.get(key, 0) + v
        return out

    def sync(self) -> None:
        for sh in self.shards:
            sh.sync()

    def clone(self) -> "ShardedBackend":
        """Deep-copy shard states into a fresh backend (fresh jit
        caches; benchmark trials use this to undo donation).  Per-shard
        RNG seeds, routing state, and the maintenance log carry over."""
        other = ShardedBackend(self.cfg, self.n_shards,
                               devices=self.devices, seed=self.seed)
        other._shards = [sh.clone() for sh in self.shards]
        for s, sh in enumerate(other._shards):
            sh.state = jax.device_put(sh.state, self.devices[s])
        other._n_routed = self._n_routed
        other._alloc = list(self._alloc)
        other.consolidations = list(self.consolidations)
        return other

    # -- durability (DESIGN.md §11) -------------------------------------------

    def save(self, ckpt_dir: str, *, lsn: int = 0,
             extra: Optional[dict] = None, meta: Optional[dict] = None,
             keep: int = 3, _pre_publish=None) -> str:
        """Atomic whole-backend checkpoint: per-shard subdirs + a
        shard-layout manifest, staged and renamed as one unit.

        Layout under ``step_<lsn>/``: ``shard_XX/`` (each shard's own
        `LSMVecIndex.save`), ``engine/`` (caller `extra` arrays),
        ``alloc.npz`` (global ids in allocation order) and
        ``layout.json`` recording shard count, routing counter and the
        covering LSN.  A restore validates the layout against the
        target config/shard count, so a checkpoint can never be loaded
        into a mis-sharded backend (routing would silently diverge).
        """
        self.sync()
        os.makedirs(ckpt_dir, exist_ok=True)
        ckpt.sweep_stale_tmp(ckpt_dir)
        final = os.path.join(ckpt_dir, f"step_{int(lsn):08d}")
        tmp = final + ".tmp"
        os.makedirs(tmp)
        for s, sh in enumerate(self.shards):
            sh.save(os.path.join(tmp, f"shard_{s:02d}"), lsn=lsn, keep=1)
        if extra:
            ckpt.save_checkpoint(
                os.path.join(tmp, "engine"), step=int(lsn),
                tree={k: np.asarray(v) for k, v in extra.items()},
                metadata={}, keep=1)
        layout = {"n_shards": self.n_shards, "cap": self.cfg.cap,
                  "dim": self.cfg.dim, "lsn": int(lsn), "seed": self.seed,
                  "n_routed": self._n_routed,
                  "consolidations": list(self.consolidations),
                  "metadata": meta or {}}
        with open(os.path.join(tmp, "alloc.npz"), "wb") as f:
            np.savez(f, alloc=np.asarray(self._alloc, np.int64))
            f.flush()
            os.fsync(f.fileno())
        with open(os.path.join(tmp, "layout.json"), "w") as f:
            json.dump(layout, f)
            f.flush()
            os.fsync(f.fileno())
        if _pre_publish is not None:
            _pre_publish()
        os.rename(tmp, final)   # atomic publish
        fd = os.open(ckpt_dir, os.O_RDONLY)
        try:
            os.fsync(fd)
        finally:
            os.close(fd)
        steps = sorted(ckpt._list_steps(ckpt_dir))
        for st in steps[:-keep]:
            shutil.rmtree(os.path.join(ckpt_dir, f"step_{st:08d}"),
                          ignore_errors=True)
        return final

    @classmethod
    def restore(cls, cfg: hnsw.HNSWConfig, ckpt_dir: str, *,
                n_shards: Optional[int] = None,
                devices: Optional[Sequence] = None,
                step: Optional[int] = None
                ) -> Tuple["ShardedBackend", dict, dict]:
        """Rebuild the backend from its latest (or `step`-th) checkpoint.

        Refuses a layout mismatch: shard count (if the caller states an
        expectation), cap/dim vs `cfg`, and each shard's covering LSN vs
        the layout's — a torn multi-shard state must never restore.
        Returns (backend, metadata, extras) like `LSMVecIndex.restore`.
        """
        if step is None:
            step = ckpt.latest_step(ckpt_dir)
            if step is None:
                raise FileNotFoundError(f"no checkpoints in {ckpt_dir}")
        path = os.path.join(ckpt_dir, f"step_{step:08d}")
        with open(os.path.join(path, "layout.json")) as f:
            layout = json.load(f)
        if n_shards is not None and n_shards != layout["n_shards"]:
            raise ValueError(f"checkpoint has {layout['n_shards']} shards, "
                             f"caller expects {n_shards}")
        if layout["cap"] != cfg.cap or layout["dim"] != cfg.dim:
            raise ValueError(
                f"checkpoint cap/dim ({layout['cap']}/{layout['dim']}) "
                f"!= config ({cfg.cap}/{cfg.dim})")
        be = cls(cfg, layout["n_shards"], devices=devices,
                 seed=int(layout["seed"]))
        shards = []
        for s in range(be.n_shards):
            sh, smd, _ = LSMVecIndex.restore(
                cfg, os.path.join(path, f"shard_{s:02d}"))
            if int(smd["lsn"]) != int(layout["lsn"]):
                raise ValueError(f"shard {s} covering lsn {smd['lsn']} != "
                                 f"layout {layout['lsn']} (torn checkpoint)")
            sh.state = jax.device_put(sh.state, be.devices[s])
            shards.append(sh)
        be._shards = shards
        be._n_routed = int(layout["n_routed"])
        be._alloc = np.load(os.path.join(path, "alloc.npz"))["alloc"].tolist()
        be.consolidations = [int(c) for c in layout["consolidations"]]
        extras = {}
        eng_dir = os.path.join(path, "engine")
        if os.path.isdir(eng_dir):
            extras, _, _ = ckpt.load_arrays(eng_dir)
        metadata = {**layout["metadata"], "lsn": int(layout["lsn"])}
        return be, metadata, extras

    # -- aggregate accounting -------------------------------------------------

    def reset_stats(self) -> None:
        for sh in self.shards:
            sh.reset_stats()

    def io_cost(self, model=None) -> float:
        from repro.core import iostats
        model = model or iostats.DISK
        return sum(sh.io_cost(model) for sh in self.shards)

    def memory_breakdown(self):
        mem = self.shards[0].memory_breakdown()
        for sh in self.shards[1:]:
            mem = mem + sh.memory_breakdown()
        return mem

    def memory_bytes(self) -> int:
        return sum(sh.memory_bytes() for sh in self.shards)

    @property
    def size(self) -> int:
        return sum(sh.size for sh in self.shards)

    @property
    def n_tombstones(self) -> int:
        return sum(sh.n_tombstones for sh in self.shards)
