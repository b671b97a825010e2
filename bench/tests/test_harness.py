"""Tests of the benchmark's own code, on the CPU at small sizes.

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest -q bench/tests

They cover the trace reduction (on a small trace recorded on a v5e
chip, `data/small.xplane.pb`, written by `record_trace.py`), the peaks
table, the device guard, the generators, the reference, the proxy's
log, and the comparison: once with the control in the program's place
and once for each fault the cells can have, each of which has to come
out not correct.
"""

import json
import os
import shutil
import subprocess
import sys

import jax
import numpy as np
import pytest

from harness import check, control, data, peaks, reference, spec, trace
from harness.main import main
from harness.proxy import BackendProxy

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
TRACE = os.path.join(BENCH, "tests", "data", "small.xplane.pb")
SIFT = "sift1m-l2.search-closed"
GATHER = spec.reader("gather_l2_ms_per_query").__globals__["PATTERN"]


# -- peaks and the device guard -------------------------------------------------

def test_peaks_known_chip():
    assert peaks.peak("TPU v5 lite")["hbm_bytes_per_s"] == 819e9


def test_unknown_device_kind_raises():
    with pytest.raises(peaks.UnknownDevice):
        peaks.peak("TPU v99")


def _run(cwd, *args):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    return subprocess.run([sys.executable, "bench/run.py", *args], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=300)


def test_exits_nonzero_without_tpu():
    p = _run(ROOT, "--workload", SIFT, "--seed", "1", "--seconds", "1",
             "--trace", "0")
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert '"correct"' not in p.stdout


def test_exits_nonzero_without_the_program(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path, "--workload", SIFT, "--seed", "1", "--seconds", "1",
             "--trace", "0")
    assert p.returncode != 0
    assert '"correct"' not in p.stdout


# -- generators -------------------------------------------------------------

CONF = {"dim": 16, "data": {"clusters": 8, "center_seed": 3, "data_seed": 9,
                            "scale": 2.5, "noise": 1.0}}


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 5, 2**64 + 3, -4])
def test_deployment_repeats_exactly(seed):
    def dep(s):
        return data.Deployment(CONF, s, n_base=64, n_queries=32, n_warm=8)
    a, b, c = dep(seed), dep(seed), dep(seed + 1)
    for x, y in [(a.base, b.base), (a.queries, b.queries),
                 (a.warm_queries, b.warm_queries)]:
        assert x.dtype == np.float32 and np.array_equal(x, y)
    # another seed: the configuration's data set, queries in another order
    for x, y in [(a.base, c.base), (a.warm_queries, c.warm_queries)]:
        assert np.array_equal(x, y)
    assert a.build_seed == c.build_seed
    assert not np.array_equal(a.queries, c.queries)
    assert np.array_equal(np.unique(a.queries, axis=0),
                          np.unique(c.queries, axis=0))
    assert not (a.warm_queries[:, None] == a.queries[None]).all(-1).any()
    other = {**CONF, "data": {**CONF["data"], "data_seed": 10}}
    d = data.Deployment(other, seed, n_base=64, n_queries=32)
    assert not np.array_equal(a.base, d.base)


# -- the reference ---------------------------------------------------------------

def test_reference_agrees_with_numpy():
    rng = np.random.default_rng(0)
    table = rng.normal(size=(700, 24)).astype(np.float32)
    qs = rng.normal(size=(150, 24)).astype(np.float32)
    live = rng.random(700) < 0.7
    ids, dists = reference.knn(table, qs, live, 10)
    d = ((qs[:, None, :].astype(np.float64) - table[None]) ** 2).sum(-1)
    d[:, ~live] = np.inf
    want = np.argsort(d, axis=1, kind="stable")[:, :10]
    assert np.array_equal(np.sort(ids, 1), np.sort(want, 1))
    np.testing.assert_allclose(dists, np.take_along_axis(d, want, 1),
                               rtol=1e-4)
    np.testing.assert_allclose(reference.exact_dists(table, qs, ids),
                               np.take_along_axis(d, ids, 1), rtol=1e-12)
    assert reference.recall(ids, want).mean() == 1.0


def test_lower_precision_control_reads_wider_gaps():
    rng = np.random.default_rng(1)
    table = (rng.normal(size=(2000, 64)) * 3 + 5).astype(np.float32)
    qs = (rng.normal(size=(64, 64)) * 3 + 5).astype(np.float32)
    live = np.ones(2000, bool)
    gaps = {}
    for p in ("highest", "high"):
        ids, d = reference.knn(table, qs, live, 10, precision=p)
        gaps[p] = check.dist_rel_err(d, reference.exact_dists(table, qs, ids))
    assert 3 * gaps["highest"] < gaps["high"]


# -- the proxy's log -------------------------------------------------------------

def test_proxy_log_rebuilds_the_live_set():
    from repro.core import hnsw
    from repro.core.index import LSMVecIndex
    from repro.serve import ServeConfig, ServeEngine

    rng = np.random.default_rng(2)
    base = rng.normal(size=(300, 16)).astype(np.float32)
    cfg = hnsw.HNSWConfig(cap=1024, dim=16, ef_search=32, ef_construction=32)
    proxy = BackendProxy(LSMVecIndex.build(cfg, base))
    eng = ServeEngine(proxy, ServeConfig(query_batch=16, insert_batch=16,
                                         delete_batch=16))
    for step in range(6):
        for x in rng.normal(size=(20, 16)).astype(np.float32):
            eng.submit_insert(x)
        for e in rng.choice(300 + 20 * step, 15, replace=False):
            eng.submit_delete(int(e))
        for q in rng.normal(size=(10, 16)).astype(np.float32):
            eng.submit_query(q)
        eng.drain()
    table, masks = reference.table_and_live(proxy.log, base)
    st = proxy.state
    n = len(table)
    held = np.asarray(st.levels[:n]) >= 0
    live = held & ~np.asarray(st.tombstone[:n])
    last = masks[max(masks)]
    # deletes after the last search are not in its mask; replay them all
    for c in proxy.log:
        if c.kind == "delete":
            last[c.ids[c.ids >= 0]] = False
    assert np.array_equal(last, live)
    assert np.array_equal(table, np.asarray(st.vectors[:n]))


# -- the trace reduction ---------------------------------------------------------

@pytest.fixture(scope="module")
def events():
    return trace.load(TRACE)


def test_trace_has_device_ops_and_spans(events):
    assert list(events.ops) == ["/device:TPU:0"]
    assert len(events.spans["bench.search"]) == 2
    assert len(events.spans["bench.window"]) == 1


def test_trace_busy_and_idle(events):
    win = events.spans["bench.window"][0]
    busy = trace.busy_ns(events, win)
    assert 0 < busy < win[1] - win[0]
    inside = trace.busy_ns(events, win, within=events.spans["bench.search"])
    assert 0 < inside <= busy
    gaps = trace.idle_gaps(events, win)
    assert gaps and all(g[1] > 0 for g in gaps)
    assert sum(g[1] for g in gaps) <= (win[1] - win[0] - busy) / 1e9 + 1e-9


def test_trace_kernel_time_by_name(events):
    win = events.spans["bench.window"][0]
    read = spec.reader("gather_l2_ms_per_query")
    g = trace.kernel_ns(events, GATHER, win,
                        within=events.spans["bench.search"])
    assert g > 0
    assert g == trace.kernel_ns(events, "tpu_custom_call", win)
    assert g < trace.busy_ns(events, win)

    class R:
        pass
    run = R()
    run.events, run.window_ns = events, win
    run.log_traced = [type("C", (), {"kind": "search", "keys": [0] * 8})()
                      for _ in range(2)]
    assert read(run) == pytest.approx(g / 1e6 / 16)
    top = trace.top_ops(events, win)
    assert 0 < len(top) <= 10 and top[0][1] >= top[-1][1] > 0
    assert any(name.endswith("tpu_custom_call") for name, _ in top)


def test_search_device_time_reader(events):
    win = events.spans["bench.window"][0]
    read = spec.reader("search_device_ms_per_query")
    spans = trace.whole_spans(events, "bench.search", win)
    assert spans == events.spans["bench.search"]
    assert trace.whole_spans(events, "bench.search",
                             (spans[0][0] + 1, win[1])) == spans[1:]

    class R:
        pass
    run = R()
    run.events, run.window_ns = events, win
    run.log_traced = [type("C", (), {"kind": "search", "keys": [0] * 8})()
                      for _ in range(2)]
    busy = trace.busy_ns(events, win, within=spans)
    assert read(run) == pytest.approx(busy / 1e6 / 16)
    run.log_traced = []
    assert read(run) is None


def test_listed_metric_that_reads_nothing_is_an_error(events, capsys):
    from harness import main as hm
    cell = spec.load(SIFT)

    class R:
        pass
    run = R()
    run.cell, run.events, run.window_ns = cell, events, (0, 1)
    run.log_traced = run.log_window = []
    run.metrics0 = run.metrics1 = {"query": {"batches": 0, "count": 0}}
    run.setup = {"build_s": 1.0, "compile_s": 2.0}
    rec = hm.read_layers(run)
    assert rec["build_s"] == {"value": 1.0, "unit": "s"}
    assert "search_device_ms_per_query" not in rec
    err = capsys.readouterr().err
    assert ("error: per-layer metric search_device_ms_per_query is listed "
            f"for {SIFT} and found nothing to read") in err


def test_interval_union_and_overlap():
    u = trace.union([(5, 7), (0, 2), (1, 3), (7, 9)])
    assert u == [[0, 3], [5, 9]]
    assert trace.overlap(u, [[2, 6]]) == 2


def test_search_roofline_reader(events):
    win = events.spans["bench.window"][0]
    read = spec.reader("search_roofline")
    cell = spec.load(SIFT)

    class R:
        pass
    run = R()
    run.cell, run.events, run.window_ns = cell, events, win
    run.peaks = peaks.peak("TPU v5 lite")
    run.log_traced = [type("C", (), {"kind": "search", "keys": [0] * 8,
                                     "io": {"n_vec": 8000, "n_adj": 800}})()]
    v = read(run)
    busy = trace.busy_ns(events, win, within=events.spans["bench.search"])
    want = 100 * (8000 * 128 * 4 + 800 * 16 * 4) / 819e9 / (busy / 1e9)
    assert v == pytest.approx(want)
    run.log_traced = []
    assert read(run) is None


# -- the comparison: the control and the faults ------------------------------------

def test_control_comes_out_not_correct():
    c = spec.load(SIFT)
    assert control.readings(c, 3, n_queries=128, n_base=8192,
                            precision="highest")["correct"]
    r = control.readings(c, 3, n_queries=128, n_base=8192)
    assert not r["correct"]
    assert r["dist_rel_err"] > c.config["check"]["dist_rel_err_max"]


SMALL = {"n_base": 2048, "cap": 8192}


def _main(cell, capsys, fault=None, seconds="4"):
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(ROOT, "bench_out", "test_cache"))
    rc = main(["--workload", cell, "--seed", "2147483653", "--seconds",
               seconds, "--trace", "0"],
              require=lambda c: jax.devices()[:c], sizes=SMALL, fault=fault)
    assert rc == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert list(out)[:5] == ["correct", "attempted", "failed", "metrics",
                             "device"]
    assert list(out)[-1] == "checks"
    return out


def _altered_answer(proxy):
    search = proxy.search

    def bad(queries, k=None, *, params=None):
        res = search(queries, k, params=params)
        ids = np.array(res.ids)
        ids[0, 0] = ids[0, -1]            # one answer altered
        return type(res)(ids=ids, dists=res.dists)
    proxy.search = bad


def _half_batch(proxy):
    search = proxy.search

    def bad(queries, k=None, *, params=None):
        res = search(queries, k, params=params)
        ids, d = np.array(res.ids), np.array(res.dists)
        ids[len(ids) // 2:] = -1
        d[len(d) // 2:] = np.inf
        return type(res)(ids=ids, dists=d)
    proxy.search = bad


def test_sound_run_is_correct(capsys):
    out = _main(SIFT, capsys)
    assert out["correct"] is True
    assert set(out["metrics"]) == {"queries_per_s", "query_p95_ms",
                                   "recall_at_10", "setup_s"}
    assert out["checks"]["unanswered"]["value"] == 0


@pytest.mark.parametrize("fault", [_altered_answer, _half_batch])
def test_fault_comes_out_not_correct(capsys, fault):
    out = _main(SIFT, capsys, fault=fault)
    assert out["correct"] is False


def test_answers_that_came_during_a_stall_are_settled():
    """A traced run's client stalls while the profiler stops; answers
    that arrived meanwhile count, however late the client looks."""
    from harness import serve
    from repro.serve.request import Ticket

    r = serve.Request(np.zeros(4, np.float32), due=0.0)
    r.ticket = Ticket()
    r.ticket._complete("answer")
    pending = [r]
    serve._wait_all(pending, deadline=serve.clock() - 1.0)
    assert pending == [] and r.value == "answer" and r.error is None
