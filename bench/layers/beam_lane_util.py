"""Graph search (`core/traversal.py`): the share of the beam loop's
lanes that did work — nodes expanded over the expansions the loop had
room for (trips x lanes dispatched, padded ones included, x nodes popped
per trip), over the query batches between the snapshots around the
window (`ServeMetrics` beam counters).  At most 1: a lane expands at
most its width on a trip."""


def read(run):
    b0, b1 = run.metrics0.get("beam"), run.metrics1.get("beam")
    if b0 is None or b1 is None:
        return None
    slots = b1["slots"] - b0["slots"]
    return (b1["hops"] - b0["hops"]) / slots if slots > 0 else None
