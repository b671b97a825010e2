"""Serving engine (`serve/scheduler.py`, `serve/queue.py`): the mean
share of the 128-wide query batch that real queries filled in the
window, from `ServeMetrics` counts (query count / batches / batch cap)."""


def read(run):
    q0, q1 = run.metrics0["query"], run.metrics1["query"]
    batches = q1["batches"] - q0["batches"]
    if batches <= 0:
        return None
    cap = run.cell.config["serve"]["query_batch"]
    return (q1["count"] - q0["count"]) / batches / cap
