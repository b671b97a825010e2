"""Spans and counters inside the serving path.

- `ServeMetrics` queue wait: engine clock at the pop of a batch minus
  each request's enqueue time, summed per op beside the request count;
- the beam loop's own trip counter, reduced on the device per batch to
  (trips, hops, slots) and fetched in `collect()` as `SearchResult.beam`;
- the host spans ``lsmvec.search.dispatch`` and ``lsmvec.search.collect``
  in a profiler trace, nested inside the caller's span.
"""

import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import BeamCounters, HNSWConfig, LSMVecIndex, SearchParams
from repro.core.index import jit_programs
from repro.data.synth import make_clustered_vectors
from repro.kernels.beam.ops import beam_iter_cap
from repro.serve import MaintenancePolicy, ServeConfig, ServeEngine

CFG = HNSWConfig(cap=1024, dim=16, M=8, M_up=4, num_upper=2,
                 ef_search=32, ef_construction=32, k=5,
                 rho=1.0, use_filter=False, lsm_mem_cap=128,
                 lsm_levels=2, lsm_fanout=8, batch_expand=4)
NO_MAINT = MaintenancePolicy(tombstone_ratio=None, heat_budget=None)


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


def make_data(n, seed):
    return make_clustered_vectors(n, dim=16, seed=seed)


@pytest.fixture(scope="module")
def small_index():
    return LSMVecIndex.build(CFG, make_data(400, seed=0))


class _SlowBackend:
    """Delegates to the index; each search advances the fake clock, so
    service time shows in latency and not in queue wait."""

    def __init__(self, backend, clock, service_s):
        self._b, self._clock, self._service = backend, clock, service_s

    def __getattr__(self, name):
        return getattr(self._b, name)

    def search(self, queries, k=None, *, params=None):
        self._clock.t += self._service
        return self._b.search(queries, k, params=params)


def test_queue_wait_is_exact_under_the_fake_clock(small_index):
    clock = FakeClock()
    eng = ServeEngine(
        _SlowBackend(small_index, clock, service_s=10.0),
        ServeConfig(query_batch=4, insert_batch=4, delete_batch=4,
                    query_window=0.0, insert_window=0.0, delete_window=0.0,
                    adaptive_windows=False, maintenance=NO_MAINT),
        clock=clock)
    qs = make_data(3, seed=1)
    for t, q in zip((0.0, 1.0, 2.0), qs):
        clock.t = t
        eng.submit_query(q)
    clock.t = 5.0
    assert eng.pump(force=True) is not None
    m = eng.metrics.snapshot()
    assert m["query"]["count"] == 3
    assert m["query"]["queue_wait_s"] == pytest.approx(5 + 4 + 3)
    # latency runs to completion, after the 10 s of service
    assert m["query"]["p99_ms"] > 13_000
    # per op: the insert's wait is its own
    clock.t = 20.0
    eng.submit_insert(make_data(1, seed=2)[0])
    clock.t = 20.5
    assert eng.pump(force=True) is not None
    m = eng.metrics.snapshot()
    assert m["insert"]["count"] == 1
    assert m["insert"]["queue_wait_s"] == pytest.approx(0.5)
    assert m["query"]["queue_wait_s"] == pytest.approx(12.0)


def test_engine_sums_the_backends_beam_counters(small_index):
    eng = ServeEngine(small_index,
                      ServeConfig(query_batch=8, maintenance=NO_MAINT),
                      clock=FakeClock())
    qs = make_data(12, seed=3)
    for q in qs:
        eng.submit_query(q)
    eng.drain()
    beam = eng.metrics.snapshot()["beam"]
    assert beam["batches"] == 2
    p = SearchParams(use_snapshot=True, pad_to=8, record_heat=False)
    want = [small_index.search(qs[:8], params=p).beam,
            small_index.search(qs[8:], params=p).beam]
    assert beam["trips"] == sum(b.trips for b in want)
    assert beam["hops"] == sum(b.hops for b in want)
    assert beam["slots"] == sum(b.slots for b in want)


def _lanes(idx, qs, width, n_expand):
    """Per-lane results and the batch's counters of the serving jit."""
    padded = np.zeros((width, qs.shape[1]), np.float32)
    padded[:len(qs)] = qs
    valid = np.arange(width) < len(qs)
    res, _, beam = jit_programs(CFG).search_snap(
        idx.state, jnp.asarray(padded), jnp.asarray(valid), idx.snapshot(),
        1.0, False, CFG.ef_search, n_expand, False)
    return jax.device_get(res), BeamCounters(*np.asarray(beam).tolist())


def test_one_node_per_trip_trips_equal_max_lane_hops(small_index):
    qs = make_data(10, seed=4)
    res, beam = _lanes(small_index, qs, 16, n_expand=1)
    hops = np.asarray(res.stats.n_hops)
    np.testing.assert_array_equal(np.asarray(res.trips), hops)
    assert beam.trips == hops.max() > 0
    assert beam.hops == hops.sum()
    p = SearchParams(n_expand=1, pad_to=16)
    assert small_index.search(qs, params=p).beam == beam


@pytest.mark.parametrize("n_expand", [1, 4])
def test_lane_use_is_at_most_one(small_index, n_expand):
    qs = make_data(10, seed=5)
    p = SearchParams(n_expand=n_expand, pad_to=16)
    beam = small_index.search(qs, params=p).beam
    assert 0 < beam.hops <= beam.slots
    assert beam.slots == beam.trips * 16 * n_expand
    if n_expand > 1:
        res, _ = _lanes(small_index, qs, 16, n_expand)
        trips = np.asarray(res.trips)[:10]
        hops = np.asarray(res.stats.n_hops)[:10]
        assert (trips < hops).all()
        assert beam.trips < beam.hops


def test_padded_lanes_count_in_slots(small_index):
    qs = make_data(10, seed=6)
    tight = small_index.search(qs, params=SearchParams(pad_to=10)).beam
    padded = small_index.search(qs, params=SearchParams(pad_to=16)).beam
    # the padded lanes do no work but the device runs them
    assert (padded.trips, padded.hops) == (tight.trips, tight.hops)
    assert tight.slots == tight.trips * 10
    assert padded.slots == padded.trips * 16


def test_fused_kernel_counts_its_trip_cap():
    """The fused kernel's loop runs every lane for the whole trip cap."""
    cfg = CFG._replace(fused_beam=True)
    idx = LSMVecIndex.build(cfg, make_data(300, seed=8))
    qs = make_data(6, seed=9)
    for n_expand in (1, 4):
        p = SearchParams(n_expand=n_expand, pad_to=8)
        beam = idx.search(qs, params=p).beam
        assert beam.trips == beam_iter_cap(2 * cfg.ef_search, n_expand,
                                           cfg.ef_search)
        assert beam.slots == beam.trips * 8 * n_expand
        assert 0 < beam.hops <= beam.slots


class _SpannedBackend:
    """The caller's own span around each search call."""

    def __init__(self, backend):
        self._b = backend

    def __getattr__(self, name):
        return getattr(self._b, name)

    def search(self, queries, k=None, *, params=None):
        with jax.profiler.TraceAnnotation("caller.search"):
            return self._b.search(queries, k, params=params)


def test_dispatch_and_collect_spans_nest_in_the_callers_span(small_index,
                                                             tmp_path):
    eng = ServeEngine(_SpannedBackend(small_index),
                      ServeConfig(query_batch=4, maintenance=NO_MAINT),
                      clock=FakeClock())
    qs = make_data(8, seed=7)
    eng.submit_query(qs[0])
    eng.drain()                       # compile outside the trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        for q in qs:
            eng.submit_query(q)
        eng.drain()                   # two full batches: two searches
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"),
                     recursive=True)[0]
    spans: dict = {}
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name in ("caller.search", "lsmvec.search.dispatch",
                              "lsmvec.search.collect"):
                    spans.setdefault(e.name, []).append(
                        (e.start_ns, e.start_ns + e.duration_ns))
    calls = sorted(spans["caller.search"])
    assert len(calls) == 2
    for name in ("lsmvec.search.dispatch", "lsmvec.search.collect"):
        assert len(spans[name]) == 2
        for a, b in calls:
            assert sum(a <= x and y <= b for x, y in spans[name]) == 1
    for (a, b), (d0, d1), (c0, c1) in zip(
            calls, sorted(spans["lsmvec.search.dispatch"]),
            sorted(spans["lsmvec.search.collect"])):
        assert a <= d0 <= d1 <= c0 <= c1 <= b
