"""Device: the share of the traced window in which no operation ran on
the chip (1 - union of device-op intervals / window)."""

from harness import trace


def read(run):
    if run.events is None:
        return None
    w = run.window_ns[1] - run.window_ns[0]
    return 100.0 * (1.0 - trace.busy_ns(run.events, run.window_ns) / w)
