"""Serving engine (`serve/scheduler.py`): mean time a query waited in
the engine's queue, from its enqueue to the pump popping its batch, over
the queries the engine answered between the snapshots around the window
(`ServeMetrics`: summed wait over query count)."""


def read(run):
    q0, q1 = run.metrics0["query"], run.metrics1["query"]
    if "queue_wait_s" not in q1:
        return None
    n = q1["count"] - q0["count"]
    if n <= 0:
        return None
    return (q1["queue_wait_s"] - q0["queue_wait_s"]) / n * 1e3
