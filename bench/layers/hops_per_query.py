"""Graph search (`core/hnsw.py`, `core/traversal.py`): beam expansions
per query, from the backend's `io_stats.n_hops` counted around each
search call of the window, over the queries those calls carried."""


def read(run):
    calls = [c for c in run.log_window if c.kind == "search" and c.io]
    n = sum(len(c.keys) for c in calls)
    return sum(c.io["n_hops"] for c in calls) / n if n else None
