"""The comparison that decides `correct`.

What the timed path produced, as the clients received it, against the
plain reference (`reference.py`), once the window has closed:

- ``recall_at_10``: mean recall@k of the served queries against exact
  k-NN over the live set that each query's batch saw.  Its floor is the
  one the configuration states (``check.recall_at_10_min``).
- ``dist_rel_err``: the widest relative gap between a served distance
  and the exact float64 distance of the id served beside it.  An answer
  altered where it is produced, or distances computed below float32,
  shows here.  Its limit (``check.dist_rel_err_max``) is set from the
  program's readings and the control's, as PERF.md records.
- ``deleted_returned``: served ids that were not live when the query's
  batch ran (deleted, or never allocated).  Exact: 0.
- ``unanswered``: requests that never resolved, or resolved with an
  error.  Exact: 0.
- ``unmatched``: served queries that no logged search call carried.
  Exact: 0.
"""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np

from harness import reference


@dataclass
class Number:
    name: str
    value: float
    limit: float
    rule: str          # "min" (value >= limit) or "max" (value <= limit)

    @property
    def ok(self) -> bool:
        if self.value != self.value:            # NaN never passes
            return False
        return self.value >= self.limit if self.rule == "min" \
            else self.value <= self.limit

    def line(self) -> str:
        op = ">=" if self.rule == "min" else "<="
        return (f"check {self.name} = {self.value!r} (limit {op} "
                f"{self.limit!r}): {'ok' if self.ok else 'FAILED'}")


def match_calls(log: list, served: list) -> list:
    """For each served query request, the index of the search call that
    answered it: a call containing its vector that ran between the
    request's submission and its answer."""
    by_key: dict = {}
    for i, c in enumerate(log):
        if c.kind == "search":
            for key in c.keys:
                by_key.setdefault(key, []).append(i)
    out = []
    for r in served:
        key = np.ascontiguousarray(r.payload, np.float32).tobytes()
        hits = [i for i in by_key.get(key, ())
                if log[i].t1 >= r.sent and log[i].t0 <= r.done]
        out.append(hits[-1] if hits else -1)
    return out


def answers(ext2int: dict, log: list, base: np.ndarray, queries: list,
            k: int) -> dict:
    """Served answers beside the reference's: internal ids served (via
    the engine's `ext2int` map), served and exact distances, and the
    truth over each live set."""
    table, masks = reference.table_and_live(log, base)
    calls = match_calls(log, queries)
    ext = np.stack([np.asarray(r.value.ids, np.int64)[:k] for r in queries])
    dist = np.stack([np.asarray(r.value.dists, np.float64)[:k]
                     for r in queries])
    ids = np.vectorize(lambda e: ext2int.get(int(e), -1) if e >= 0 else -1,
                       otypes=[np.int64])(ext)
    qs = np.stack([r.payload for r in queries]).astype(np.float32)
    truth = np.full((len(queries), k), -1, np.int64)
    not_live = np.zeros(len(queries), np.int64)
    for c in sorted(set(calls)):
        sel = np.flatnonzero(np.asarray(calls) == c)
        if c < 0:
            continue
        live = masks[c]
        truth[sel] = reference.knn(table, qs[sel], live, k)[0]
        got = ids[sel]
        inside = (got >= 0) & (got < len(live))
        bad = (got >= 0) & ~np.where(inside, live[np.where(inside, got, 0)],
                                     False)
        not_live[sel] = bad.sum(axis=1)
    exact = reference.exact_dists(table, qs, ids)
    return {"ids": ids, "dists": dist, "exact": exact, "truth": truth,
            "calls": np.asarray(calls), "not_live": not_live,
            "table": table, "queries": qs}


def dist_rel_err(dists: np.ndarray, exact: np.ndarray) -> float:
    """Widest |served - exact| / exact over served (query, rank) pairs
    that name an id; NaN when none does."""
    ok = np.isfinite(exact) & np.isfinite(dists)
    if not ok.any():
        return float("nan")
    gap = np.abs(dists[ok] - exact[ok]) / np.maximum(exact[ok], 1e-30)
    return float(gap.max())


def numbers(config: dict, a: dict, *, unanswered: int) -> list:
    lim = config["check"]
    out = [
        Number("recall_at_10", float(np.mean(reference.recall(a["ids"],
                                                              a["truth"]))),
               lim["recall_at_10_min"], "min"),
        Number("dist_rel_err", dist_rel_err(a["dists"], a["exact"]),
               lim["dist_rel_err_max"], "max"),
        Number("deleted_returned", float(a["not_live"].sum()), 0, "max"),
        Number("unanswered", float(unanswered), 0, "max"),
        Number("unmatched", float(np.sum(a["calls"] < 0)), 0, "max"),
    ]
    return out
