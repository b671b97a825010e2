"""The on-chip benchmark of the LSM-VEC serving path.

`run.py` is the entry point; everything that belongs to one
configuration, traffic mix or per-layer metric lives in a file of its
own (`configs/`, `traffic/`, `layers/`), found by the name that
`BENCHMARK.json` gives it.  This package is the general part: device
guard, data and traffic generators, the backend proxy that records
spans and batch order, the plain reference, the comparison that
decides `correct`, and the trace reduction.
"""
