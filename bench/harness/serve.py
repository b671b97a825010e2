"""The one traffic generator and the measured window: clients drive
`ServeEngine` through its public entry points while its own serving
thread pumps batches.

A traffic mix (`traffic/<mix>.json`) gives its parameters:

- ``clients``: callers in a closed loop, each sending its next query as
  soon as it has the previous answer (a fixed pool of retrieval
  workers);
- ``query_pool``: held-out queries the callers cycle through, in the
  order the run's seed gives them.

Every request is timed from the client's side, from when it was due
(the moment its caller had the previous answer) to when its ticket
resolved, so a stall counts against every request that waited behind
it.
"""

from __future__ import annotations

import time
from dataclasses import dataclass
from typing import Any, Optional

import numpy as np

clock = time.monotonic
POLL_S = 0.0005          # how often the client looks at its tickets
LATE_S = 60.0            # how long past the close an answer may come


@dataclass
class Request:
    payload: np.ndarray   # the query vector
    due: float            # absolute, clock()
    sent: float = 0.0
    ticket: Any = None
    done: Optional[float] = None
    value: Any = None
    error: Optional[str] = None

    @property
    def latency(self) -> float:
        return self.done - self.due


def _submit(engine, r: Request) -> None:
    r.sent = clock()
    r.ticket = engine.submit_query(r.payload)


def _settle(r: Request, now: float) -> bool:
    """Record a resolved ticket; True once it has resolved."""
    if not r.ticket.done:
        return False
    r.done = now
    try:
        r.value = r.ticket.result(0)
    except Exception as e:     # a failed request is counted, not raised
        r.error = f"{type(e).__name__}: {e}"
    return True


def _wait_all(pending: list, deadline: float) -> None:
    """Settle what has been answered, waiting until `deadline` for the
    rest (at least one look, however late)."""
    while pending:
        now = clock()
        pending[:] = [r for r in pending if not _settle(r, now)]
        if now >= deadline:
            return
        time.sleep(POLL_S)


def _stop(tracer) -> float:
    """Stop a traced run's profiler at the close; the seconds it took,
    which the wait for late answers does not count against them."""
    if tracer is None:
        return 0.0
    t = clock()
    tracer.stop()
    return clock() - t


@dataclass
class Window:
    t0: float
    t1: float                 # close of the window
    requests: list            # every Request sent


def closed_loop(engine, queries: np.ndarray, clients: int,
                seconds: float, tracer=None) -> Window:
    """`clients` callers; each sends its next query (cycling through
    `queries`) as soon as it has its answer.  The window closes at the
    first completion at or after `seconds`, so no batch is counted by
    halves.  `tracer` (traced run) is started from this loop and
    stopped at the close."""
    nxt = 0
    out, pending = [], []

    def send(due):
        nonlocal nxt
        r = Request(queries[nxt % len(queries)], due)
        nxt += 1
        _submit(engine, r)
        out.append(r)
        pending.append(r)

    t0 = clock()
    for _ in range(clients):
        send(t0)
    t1 = None
    while t1 is None:
        time.sleep(POLL_S)
        now = clock()
        if tracer is not None:
            tracer.maybe_start(now - t0)
        finished = [r for r in pending if _settle(r, now)]
        if not finished:
            if now - t0 >= seconds + LATE_S:     # nothing answers
                t1 = now
            continue
        pending[:] = [r for r in pending if r.done is None]
        if now - t0 >= seconds:
            t1 = now
            break
        for _ in finished:
            send(now)
    _wait_all(pending, t1 + LATE_S + _stop(tracer))
    return Window(t0, t1, out)
