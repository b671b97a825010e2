"""Benchmark entrypoint: one function per paper figure/table.

Prints CSV (`name,value,detail` lines) for Fig. 5/6/7/8 reproductions, the
kernel microbenchmarks, and — when results/dryrun.json exists — the
roofline table.  `PYTHONPATH=src python -m benchmarks.run`
"""

from __future__ import annotations

import os
import time

import jax
import jax.numpy as jnp
import numpy as np


def bench_kernels():
    """Kernel wrapper micro-timings (CPU oracle path; TPU is the target)."""
    from repro.kernels import (collision_count, gather_l2, l2_distance,
                               simhash_encode)
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(0, 1, (32, 128)), jnp.float32)
    tbl = jnp.asarray(rng.normal(0, 1, (4096, 128)), jnp.float32)
    ids = jnp.asarray(rng.integers(0, 4096, (32, 16)), jnp.int32)
    proj = jnp.asarray(rng.normal(0, 1, (64, 128)), jnp.float32)

    def t(fn, *a, **kw):
        jax.block_until_ready(fn(*a, **kw))   # compile
        t0 = time.perf_counter_ns()
        for _ in range(10):
            jax.block_until_ready(fn(*a, **kw))
        return (time.perf_counter_ns() - t0) / 10 / 1e3

    print(f"kernel,l2_distance_32x4096_us,{t(l2_distance, q, tbl):.1f}")
    print(f"kernel,gather_l2_32x16_us,{t(gather_l2, q, tbl, ids):.1f}")
    codes = simhash_encode(tbl, proj)
    cq = simhash_encode(q, proj)
    print(f"kernel,simhash_encode_4096_us,"
          f"{t(simhash_encode, tbl, proj):.1f}")
    print(f"kernel,collision_count_32x4096_us,"
          f"{t(collision_count, cq, codes, 64):.1f}")


def main() -> None:
    t_start = time.monotonic()
    print("name,value,detail")

    bench_kernels()

    from benchmarks import (fig5_workloads, fig6_memory, fig7_tradeoff,
                            fig8_sampling)
    # protocol-faithful sizes that complete on one CPU core; pass larger
    # n_base/n_batches for the paper-scale sweep on real hardware
    wl = dict(n_base=2048, n_batches=5)
    _, ok5 = fig5_workloads.main(**wl)
    # fig6 is now the tier sweep (BENCH_memory.json); smoke instance here,
    # `python benchmarks/fig6_memory.py` for the committed full run
    doc6 = fig6_memory.run(**fig6_memory.smoke_args(0))
    ok6 = all(doc6["criteria"].values())
    _, ok7 = fig7_tradeoff.main()
    _, ok8 = fig8_sampling.main()

    if os.path.exists("results/dryrun.json"):
        from benchmarks import roofline
        roofline.main()

    status = all([ok5, ok6, ok7, ok8])
    print(f"\nsummary,paper_claims,"
          f"{'ALL-PASS' if status else 'SOME-FAIL'}")
    print(f"summary,total_wall_s,{time.monotonic() - t_start:.1f}")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
