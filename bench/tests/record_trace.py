#!/usr/bin/env python3
"""Record the small device trace that `test_harness.py` reduces.

    python bench/tests/record_trace.py <out.xplane.pb>     # on the chip

Builds a 2,048-vector index, and traces two 8-query searches through
`LSMVecIndex` inside ``bench.window`` / ``bench.search`` host spans, as
a measured run would.  The file it writes is kept under
`bench/tests/data/`.
"""

import glob
import os
import shutil
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(os.path.dirname(HERE), "..", "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.core import hnsw  # noqa: E402
from repro.core.backend import SearchParams  # noqa: E402
from repro.core.index import LSMVecIndex  # noqa: E402


def main(out: str) -> None:
    if jax.devices()[0].platform != "tpu":
        raise SystemExit("record_trace: needs the chip")
    rng = np.random.default_rng(0)
    cfg = hnsw.HNSWConfig(cap=4096, dim=100, ef_search=32)
    idx = LSMVecIndex.build(cfg, rng.normal(size=(2048, 100)).astype(np.float32))
    qs = rng.normal(size=(8, 100)).astype(np.float32)
    p = SearchParams(use_snapshot=True, pad_to=8, record_heat=False)
    idx.search(qs, params=p)                       # compile outside the trace
    d = tempfile.mkdtemp()
    jax.profiler.start_trace(d)
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(2):
            with jax.profiler.TraceAnnotation("bench.search"):
                idx.search(qs, params=p)
    jax.profiler.stop_trace()
    src = glob.glob(os.path.join(d, "**", "*.xplane.pb"), recursive=True)[0]
    shutil.copy(src, out)
    shutil.rmtree(d)
    print(f"record_trace: {os.path.getsize(out)} bytes -> {out}")


if __name__ == "__main__":
    main(sys.argv[1])
