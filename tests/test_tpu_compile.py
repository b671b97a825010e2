"""Ahead-of-time compiles for a TPU v5e that is described, not attached.

The TPU compiler is installed with JAX, so the serving path's kernels and
the batched search program are compiled here at deployment widths (a
2^20-row table, dim 128, 128-query batches).  This catches what interpret
mode cannot — block shapes off the (8, 128) tiling, kernels that do not
lower, programs that do not fit — without a chip.  Nothing runs, so
these tests say nothing about results or times.

The topology is described inside a module fixture (never at import): only
one process may load the TPU library, and every test worker imports this
file.
"""

import math
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import hnsw, index
from repro.kernels.gather_l2 import ops as gather_ops
from repro.kernels.l2_distance import ops as l2_ops

CAP = 1 << 20
DIM = 128
QUERY_BATCH = 128


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def on_tpu(monkeypatch):
    """Steer the kernels' backend probes to the chip being compiled for
    (JAX's default backend here is still the CPU)."""
    monkeypatch.setattr(gather_ops, "_on_tpu", lambda: True)
    monkeypatch.setattr(l2_ops, "_on_tpu", lambda: True)


def _shape(one_chip, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()    # the Pallas kernel
    return compiled


def _beam_body_gathers(hlo, lanes, cap, ef):
    """Instructions of the beam `while` body (the loop that carries the
    `[lanes, cap + 1]` visited set), the computations it calls included,
    whose op_name ends in `while/body/gather` and whose result has
    lanes x ef elements: a beam-wide permutation applied by element
    gathers on every trip."""
    comps, name = {}, None
    for line in hlo.splitlines():
        m = re.match(r"(?:ENTRY )?%(\S+) \(.*\{$", line)
        if m:
            name = m.group(1)
            comps[name] = []
        elif name is not None:
            comps[name].append(line)
    carry = f"pred[{lanes},{cap + 1}]"
    todo = [re.search(r"body=%([\w.\-]+)", line).group(1)
            for lines in comps.values() for line in lines
            if " while(" in line and carry in line.split(" while(")[0]]
    assert todo, "no beam while loop carrying the visited set"
    seen, hits = set(), []
    while todo:
        c = todo.pop()
        if c in seen:
            continue
        seen.add(c)
        for line in comps[c]:
            todo += re.findall(r"(?:calls|to_apply|body|condition)=%([\w.\-]+)",
                               line)
            for group in re.findall(r"branch_computations=\{([^}]*)\}", line):
                todo += re.findall(r"%([\w.\-]+)", group)
            m = re.match(r"\s*(?:ROOT )?%\S+ = \w+\[([\d,]*)\]", line)
            if m and re.search(r'op_name="[^"]*while/body/gather"', line):
                shape = [int(x) for x in m.group(1).split(",") if x]
                if math.prod(shape) == lanes * ef:
                    hits.append(line.strip())
    return hits


def test_gather_l2_compiles(one_chip):
    _compile(lambda q, t, i: gather_ops.gather_l2(
                 q, t, i, use_pallas=True, interpret=False),
             _shape(one_chip, (QUERY_BATCH, DIM), jnp.float32),
             _shape(one_chip, (CAP, DIM), jnp.float32),
             _shape(one_chip, (QUERY_BATCH, 16), jnp.int32))


def test_gather_l2_q8_compiles(one_chip):
    _compile(lambda q, t, s, i: gather_ops.gather_l2_q8(
                 q, t, s, i, use_pallas=True, interpret=False),
             _shape(one_chip, (QUERY_BATCH, DIM), jnp.float32),
             _shape(one_chip, (CAP, DIM), jnp.int8),
             _shape(one_chip, (CAP,), jnp.float32),
             _shape(one_chip, (QUERY_BATCH, 16), jnp.int32))


def test_l2_distance_compiles(one_chip):
    _compile(lambda q, c: l2_ops.l2_distance(
                 q, c, use_pallas=True, interpret=False),
             _shape(one_chip, (64, DIM), jnp.float32),
             _shape(one_chip, (CAP, DIM), jnp.float32))


@pytest.mark.parametrize("program", ["search", "search_snap"])
def test_batched_search_compiles(one_chip, on_tpu, program):
    """The index's own jitted batched search at cap 2^20, as dispatched
    (`search`: LSM-probe adjacency; `search_snap`: the snapshot path
    `ServeEngine` serves from), with the fused gather kernel inside the
    vmapped beam loop.  The beam merge applies its order inside the sort:
    no lanes x ef element gather is left in the loop body."""
    cfg = hnsw.HNSWConfig(cap=CAP, dim=DIM)
    state = jax.tree.map(
        lambda a: _shape(one_chip, a.shape, a.dtype),
        jax.eval_shape(lambda: hnsw.init(cfg, jax.random.key(0))))
    qs = _shape(one_chip, (QUERY_BATCH, DIM), jnp.float32)
    statics = dict(rho=1.0, use_filter=True, ef=cfg.ef_search, n_expand=1,
                   record_heat=False)
    fn = getattr(index.jit_programs(cfg), program)
    if program == "search":
        lowered = fn.lower(state, qs, **statics)
    else:
        lowered = fn.lower(
            state, qs, _shape(one_chip, (QUERY_BATCH,), jnp.bool_),
            _shape(one_chip, (CAP, cfg.M), jnp.int32), **statics)
    compiled = lowered.compile()
    hlo = compiled.as_text()
    assert "tpu_custom_call" in hlo
    gathers = _beam_body_gathers(hlo, QUERY_BATCH, CAP, cfg.ef_search)
    assert not gathers, gathers[:3]
