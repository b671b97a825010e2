"""Graph search (`core/traversal.py`): trips the beam loop ran per query
batch, from the loop's own trip counter (the batch runs until its
slowest lane stops), summed by `ServeMetrics` over the query batches
between the snapshots around the window."""


def read(run):
    b0, b1 = run.metrics0.get("beam"), run.metrics1.get("beam")
    if b0 is None or b1 is None:
        return None
    n = b1["batches"] - b0["batches"]
    return (b1["trips"] - b0["trips"]) / n if n > 0 else None
