"""Kernels and search programs (`kernels/gather_l2`, the search jits):
the share of the HBM roofline the search reaches.  The bytes a search
has to move are counted from the backend's I/O counters, the same
whatever implements the search: every vector fetched (`n_vec` rows of
`dim` float32, the logical width, not the padded one) and every
adjacency row read (`n_adj` rows of `M` int32).  The least time those
bytes take at the chip's peak HBM bandwidth, over the device-busy time
inside the host spans of those same calls: the search calls that lie
wholly inside the traced part of the window."""

from harness import trace


def read(run):
    calls = [c for c in run.log_traced if c.kind == "search" and c.io]
    if not calls or run.events is None:
        return None
    cfg = run.cell.config
    need = sum(c.io["n_vec"] * cfg["dim"] * 4
               + c.io["n_adj"] * cfg["index"]["M"] * 4 for c in calls)
    spans = trace.whole_spans(run.events, "bench.search", run.window_ns)
    busy = trace.busy_ns(run.events, run.window_ns, within=spans) / 1e9
    if need <= 0 or busy <= 0:
        return None
    return 100.0 * need / run.peaks["hbm_bytes_per_s"] / busy
