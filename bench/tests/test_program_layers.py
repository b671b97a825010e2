"""Tests of the readers of the program's own spans and counters, on
hand-built snapshots and events, on the CPU.

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest -q bench/tests
"""

import os

import pytest

from harness import program_spans, spec, trace

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TRACE = os.path.join(BENCH, "tests", "data", "small.xplane.pb")
D, C = program_spans.DISPATCH, program_spans.COLLECT
MS = 1_000_000                                  # ns


class Run:
    def __init__(self, events=None, window_ns=(0, 0), m0=None, m1=None):
        self.events, self.window_ns = events, window_ns
        self.metrics0, self.metrics1 = m0, m1


def _events(*busy):
    return trace.Events(ops={"/device:TPU:0": [("op", a * MS, b * MS)
                                               for a, b in busy]})


def _idle(monkeypatch, sp, ev, window):
    monkeypatch.setattr(program_spans, "spans", lambda run: sp)
    return spec.reader("search_host_idle_ms")(Run(ev, window))


# -- search_host_idle_ms ---------------------------------------------------------

def test_known_idle_gap_inside_the_spans_is_read_back(monkeypatch):
    # call 1: dispatch [0, 20] ms, device busy from 10: 10 ms idle;
    # collect [20, 110], busy 20-50 and 60-100: 10 + 10 ms idle.
    # call 2: dispatch [200, 204] ms, all idle; collect [204, 300],
    # busy 204-298: 2 ms idle.  Idle between the calls is not theirs.
    ev = _events((10, 50), (60, 100), (204, 298))
    sp = {D: [(0, 20 * MS), (200 * MS, 204 * MS)],
          C: [(20 * MS, 110 * MS), (204 * MS, 300 * MS)]}
    got = _idle(monkeypatch, sp, ev, (0, 400 * MS))
    assert got == pytest.approx((30 + 4 + 2) / 2)
    # only the second call lies wholly inside this window
    assert _idle(monkeypatch, sp, ev, (1, 400 * MS)) == pytest.approx(6)


def test_no_whole_call_reads_none(monkeypatch):
    ev = _events((10, 50))
    sp = {D: [(0, 20 * MS)], C: [(20 * MS, 110 * MS)]}
    # the call starts before the traced part, or ends after it
    assert _idle(monkeypatch, sp, ev, (5 * MS, 400 * MS)) is None
    assert _idle(monkeypatch, sp, ev, (0, 100 * MS)) is None
    # a dispatch whose collect fell outside the trace
    assert _idle(monkeypatch, {D: sp[D]}, ev, (0, 400 * MS)) is None
    # a program without the spans, or an untraced run
    assert _idle(monkeypatch, {}, ev, (0, 400 * MS)) is None
    monkeypatch.undo()
    assert spec.reader("search_host_idle_ms")(Run()) is None


def test_sharded_calls_count_their_outermost_spans(monkeypatch):
    ev = _events((10, 50))
    # one fan-out call: the backend's spans hold each shard's
    sp = {D: [(0, 20 * MS), (1 * MS, 5 * MS), (6 * MS, 9 * MS)],
          C: [(20 * MS, 60 * MS), (21 * MS, 40 * MS), (41 * MS, 59 * MS)]}
    assert _idle(monkeypatch, sp, ev, (0, 100 * MS)) == pytest.approx(20)


def test_a_trace_without_program_spans_reads_nothing():
    # recorded before the program marked its calls
    assert program_spans.load(TRACE) == {}


# -- the counters -----------------------------------------------------------------

def _snap(count, wait_s, batches, trips, hops, slots):
    return {"query": {"count": count, "batches": batches,
                      "queue_wait_s": wait_s},
            "beam": {"batches": batches, "trips": trips, "hops": hops,
                     "slots": slots}}


def test_counter_readers_difference_the_snapshots():
    m0 = _snap(256, 3000.0, 2, 3000, 240_000, 384_000)
    m1 = _snap(256 + 768, 3000.0 + 768 * 12.0, 8,
               3000 + 6 * 1500, 240_000 + 6 * 150_000,
               384_000 + 6 * 1500 * 128)
    run = Run(m0=m0, m1=m1)
    assert spec.reader("query_queue_wait_ms")(run) == pytest.approx(12_000)
    assert spec.reader("beam_trips_per_batch")(run) == pytest.approx(1500)
    assert spec.reader("beam_lane_util")(run) == pytest.approx(
        150_000 / (1500 * 128))


def test_counter_readers_read_nothing_without_the_counters():
    plain = {"query": {"count": 10, "batches": 1}}
    run = Run(m0=plain, m1={"query": {"count": 20, "batches": 2}})
    for name in ("query_queue_wait_ms", "beam_trips_per_batch",
                 "beam_lane_util"):
        assert spec.reader(name)(run) is None
    same = _snap(10, 1.0, 1, 5, 5, 5)
    run = Run(m0=same, m1=same)
    for name in ("query_queue_wait_ms", "beam_trips_per_batch",
                 "beam_lane_util"):
        assert spec.reader(name)(run) is None


@pytest.mark.parametrize("n_expand", [1, 4])
def test_lane_use_cannot_pass_one(n_expand):
    """The program counts a slot for every node a lane could expand on a
    trip, so lane use stays at most 1 whatever the batch."""
    import numpy as np

    from repro.core import HNSWConfig, LSMVecIndex, SearchParams
    from repro.serve import MaintenancePolicy, ServeConfig, ServeEngine

    rng = np.random.default_rng(0)
    cfg = HNSWConfig(cap=1024, dim=16, M=8, ef_search=32,
                     ef_construction=32, k=5)
    idx = LSMVecIndex.build(cfg, rng.normal(size=(300, 16)).astype(
        np.float32))
    eng = ServeEngine(idx, ServeConfig(
        query_batch=8, search=SearchParams(n_expand=n_expand),
        maintenance=MaintenancePolicy(tombstone_ratio=None,
                                      heat_budget=None)))
    m0 = eng.metrics.snapshot()
    for q in rng.normal(size=(21, 16)).astype(np.float32):
        eng.submit_query(q)
    eng.drain()
    run = Run(m0=m0, m1=eng.metrics.snapshot())
    assert 0 < spec.reader("beam_lane_util")(run) <= 1
    assert spec.reader("beam_trips_per_batch")(run) > 0
