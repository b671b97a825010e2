"""Roofline reports: the model-dryrun table and the beam-megakernel
bytes-moved model (DESIGN.md §15).

Legacy mode (default) reads results/dryrun.json and prints the per-cell
table:

    compute term    = per-device HLO FLOPs / 197 TFLOP/s (bf16)
    memory term     = per-device HLO bytes / 819 GB/s HBM
    collective term = per-device collective bytes / 50 GB/s ICI
                      (all-reduce counted 2x for the ring)

Plus MODEL_FLOPS = 6*N_active*D (train) / 2*N_active*D (serve) and the
useful-compute ratio MODEL_FLOPS / HLO_FLOPs.

``roofline.py beam`` instead models and measures the fused beam-search
megakernel against the per-hop-launch `while_loop` path and emits
``BENCH_roofline.json``:

  - **iostats** — measured per-query hop/row counts from a real beam
    search over a built index (the traffic terms below scale by these,
    not by worst-case loop caps);
  - **model** — bytes moved per query under both execution models at
    the TPU HBM ceiling (819 GB/s) plus a per-launch overhead term.
    Both paths stream the same adjacency/vector/code rows; the per-hop
    model additionally spills the beam heap and visited bitmap to HBM
    between launches and pays ~4 launches per hop (pop, adjacency
    gather, fused distance, merge), while the megakernel keeps heap and
    visited VMEM-resident across the whole loop and pays one launch per
    query block (DESIGN.md §15 derives both);
  - **measured** — wall-clock A/B of the two paths on this host.  On a
    CPU host both arms lower to `while_loop` HLO (the oracle route), so
    the measured ratio hovers near 1.0 and only the model halves carry
    the TPU claim; the backend is recorded so readers can tell.

``--smoke`` shrinks the instance; ``--check`` validates the schema and
gates on the model invariant (megakernel strictly fewer bytes and
launches than per-hop) plus measured id parity — the CI mode.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

# hardware ceilings shared with the dryrun table below
TFLOPS_BF16 = 197e12
HBM_GBS = 819e9
ICI_GBS = 50e9
LAUNCH_US = 3.0          # conservative per-kernel-launch overhead
LAUNCHES_PER_HOP = 4     # pop/top_k, adjacency gather, gather_l2, merge

BEAM_SCHEMA = {
    "meta": ("mode", "backend", "n_base", "dim", "dpad", "batch", "ef",
             "M", "m_bits", "n_expand", "config"),
    "iostats": ("hops_per_query", "adj_rows_per_query",
                "vec_rows_per_query", "filtered_per_query"),
    "model": ("hbm_bw_gbs", "launch_overhead_us", "launches_per_hop",
              "per_hop", "megakernel", "bytes_ratio", "t_ratio"),
    "measured": ("while_p50_us_per_query", "fused_p50_us_per_query",
                 "ratio", "parity"),
}


def validate_beam_schema(doc: dict) -> None:
    for section, fields in BEAM_SCHEMA.items():
        if section not in doc:
            raise ValueError(f"missing section {section!r}")
        for f in fields:
            if f not in doc[section]:
                raise ValueError(f"missing field {section}.{f}")
    for arm in ("per_hop", "megakernel"):
        for f in ("bytes_per_query", "launches_per_query", "t_model_us"):
            v = doc["model"][arm][f]
            if not isinstance(v, (int, float)) or not np.isfinite(v):
                raise ValueError(f"non-finite model.{arm}.{f}: {v!r}")
    if not isinstance(doc["measured"]["parity"], bool):
        raise ValueError("measured.parity must be bool")


def beam_bytes_model(*, hops: float, adj_rows: float, vec_rows: float,
                     ef: int, M: int, cap: int, dpad: int, m_bits: int,
                     n_expand: int) -> dict:
    """Bytes moved per query under each execution model.

    Shared streaming traffic (both models; measured row counts):
      adjacency  adj_rows x M x 4 B
      vectors    vec_rows x dpad x 4 B   (hot f32 lane; the q8 cold
                                          lane would be dpad + 4 B/row)
      codes      per hop, B*M candidate code rows x m_bits/8 B

    Per-hop-launch extra, per hop: the beam heap (ids+dists+expanded,
    ef x 9 B) and visited bitmap (cap+1 B, bool) spill to HBM on every
    launch boundary (read + write), and the query row (dpad x 4 B) is
    re-read by each distance launch.  The megakernel reads the query
    row once and keeps heap + visited in VMEM scratch for the whole
    loop (DESIGN.md §15 lays out the residency plan).
    """
    B = max(1, min(n_expand, ef))
    code_bytes = hops * B * M * (m_bits // 8)
    shared = adj_rows * M * 4 + vec_rows * dpad * 4 + code_bytes
    spill = hops * (2 * ef * 9 + 2 * (cap + 1) + dpad * 4)
    per_hop = {
        "bytes_per_query": round(shared + spill, 1),
        "launches_per_query": round(hops * LAUNCHES_PER_HOP, 2),
    }
    mega = {
        "bytes_per_query": round(shared + dpad * 4, 1),
        "launches_per_query": 1.0,
    }
    for arm in (per_hop, mega):
        arm["t_model_us"] = round(
            arm["bytes_per_query"] / HBM_GBS * 1e6
            + arm["launches_per_query"] * LAUNCH_US, 3)
    return {
        "hbm_bw_gbs": HBM_GBS / 1e9,
        "launch_overhead_us": LAUNCH_US,
        "launches_per_hop": LAUNCHES_PER_HOP,
        "per_hop": per_hop,
        "megakernel": mega,
        "bytes_ratio": round(mega["bytes_per_query"]
                             / max(per_hop["bytes_per_query"], 1e-9), 4),
        "t_ratio": round(mega["t_model_us"]
                         / max(per_hop["t_model_us"], 1e-9), 4),
    }


def run_beam(*, n_base: int, dim: int, batch: int, seed: int,
             mode: str, reps: int, trials: int = 2) -> dict:
    import jax

    from repro.core import hnsw
    from repro.core.index import LSMVecIndex
    from repro.data.synth import make_clustered_vectors

    cfg = hnsw.HNSWConfig(
        cap=n_base + 64, dim=dim, M=12, M_up=6, num_upper=2,
        ef_search=48, ef_construction=48, k=10, m_bits=64, rho=1.0,
        eps=0.1, use_filter=False, lsm_mem_cap=256, lsm_levels=2,
        lsm_fanout=8, n_expand=1, batch_expand=4)
    base = make_clustered_vectors(n_base, dim=dim, seed=seed)
    queries = make_clustered_vectors(batch, dim=dim, seed=seed + 1)
    ix = LSMVecIndex.build(cfg, base, seed=seed)
    snap = ix.snapshot()

    def arm(fused):
        c = cfg._replace(fused_beam=fused)
        return lambda: hnsw.search_batch(c, ix.state, queries,
                                         snapshot=snap)

    run_w, run_f = arm(False), arm(True)
    res_w, res_f = run_w(), run_f()                 # compile + parity
    parity = bool(np.array_equal(np.asarray(res_w.ids),
                                 np.asarray(res_f.ids)))
    st = res_f.stats
    hops = float(np.mean(np.asarray(st.n_hops)))
    adj_rows = float(np.mean(np.asarray(st.n_adj)))
    vec_rows = float(np.mean(np.asarray(st.n_vec)))
    filtered = float(np.mean(np.asarray(st.n_filtered)))
    dpad = dim + ((-dim) % 128)
    model = beam_bytes_model(
        hops=hops, adj_rows=adj_rows, vec_rows=vec_rows,
        ef=cfg.ef_search, M=cfg.M, cap=cfg.cap, dpad=dpad,
        m_bits=cfg.m_bits, n_expand=cfg.n_expand)

    def measure(fn):
        best = None
        for _ in range(trials):
            lat = []
            for _ in range(reps):
                t0 = time.monotonic()
                r = fn()
                jax.block_until_ready(r.ids)
                lat.append((time.monotonic() - t0) * 1e6 / batch)
            p50 = float(np.percentile(lat, 50))
            best = p50 if best is None else min(best, p50)
        return best

    while_us = measure(run_w)
    fused_us = measure(run_f)
    return {
        "meta": {
            "mode": mode, "backend": jax.default_backend(),
            "n_base": n_base, "dim": dim, "dpad": dpad, "batch": batch,
            "ef": cfg.ef_search, "M": cfg.M, "m_bits": cfg.m_bits,
            "n_expand": cfg.n_expand,
            "config": dict(cfg._asdict()),
        },
        "iostats": {
            "hops_per_query": round(hops, 2),
            "adj_rows_per_query": round(adj_rows, 2),
            "vec_rows_per_query": round(vec_rows, 2),
            "filtered_per_query": round(filtered, 2),
        },
        "model": model,
        "measured": {
            "while_p50_us_per_query": round(while_us, 2),
            "fused_p50_us_per_query": round(fused_us, 2),
            "ratio": round(fused_us / max(while_us, 1e-9), 3),
            "parity": parity,
        },
    }


def beam_main(argv) -> int:
    ap = argparse.ArgumentParser(
        prog="roofline.py beam",
        description="beam megakernel bytes-moved model + measurement")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny instance (the CI mode)")
    ap.add_argument("--check", action="store_true",
                    help="validate the schema and gate on the model "
                         "invariant + measured parity; exit nonzero on "
                         "breach")
    ap.add_argument("--out", default=None,
                    help="output path (default: <repo>/"
                         "BENCH_roofline.json)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    if args.smoke:
        doc = run_beam(n_base=512, dim=64, batch=32, seed=args.seed,
                       mode="smoke", reps=8)
    else:
        doc = run_beam(n_base=4096, dim=64, batch=64, seed=args.seed,
                       mode="full", reps=24)
    print(json.dumps(doc, indent=1))
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = args.out or os.path.join(root, "BENCH_roofline.json")
    # smoke writes only to an explicit --out (CI uploads its own
    # artifact); the committed JSON comes from full runs
    if not args.smoke or args.out:
        from _util import write_bench_json
        write_bench_json(out, doc)
    if args.check:
        validate_beam_schema(doc)
        m = doc["model"]
        gates = {
            "megakernel_fewer_bytes": m["bytes_ratio"] < 1.0,
            "megakernel_fewer_launches": (
                m["megakernel"]["launches_per_query"]
                < m["per_hop"]["launches_per_query"]),
            "model_time_at_or_below": m["t_ratio"] <= 1.0,
            "measured_parity": doc["measured"]["parity"],
        }
        for name, ok in gates.items():
            print(f"  {'PASS' if ok else 'FAIL'} {name}")
        if not all(gates.values()):
            return 1
        print("beam roofline: schema + gates OK")
    return 0


# ---------------------------------------------------------------------------
# legacy dryrun-table mode
# ---------------------------------------------------------------------------

def load(path: str = "results/dryrun.json"):
    with open(path) as f:
        return json.load(f)


def fmt_table(records, mesh_filter: str = "16x16"):
    rows = []
    header = ("arch", "shape", "t_compute_s", "t_memory_s",
              "t_collective_s", "dominant", "useful_ratio",
              "roofline_frac")
    for r in sorted(records, key=lambda r: (r["arch"], r["shape"])):
        if r.get("status") != "ok" or r["mesh"] != mesh_filter:
            continue
        t = r["roofline"]
        bound = max(t["t_compute"], t["t_memory"], t["t_collective"])
        # roofline fraction: useful model FLOP time over the binding term
        useful_t = (r["model_flops_per_device"] / TFLOPS_BF16) if \
            r.get("model_flops_per_device") else 0.0
        frac = useful_t / bound if bound else 0.0
        rows.append((r["arch"], r["shape"],
                     f"{t['t_compute']:.4f}", f"{t['t_memory']:.4f}",
                     f"{t['t_collective']:.4f}", r["dominant"],
                     f"{r['useful_flops_ratio']:.3f}"
                     if r.get("useful_flops_ratio") else "-",
                     f"{frac:.3f}"))
    return header, rows


def main(path: str = "results/dryrun.json"):
    records = load(path)
    for mesh in ("16x16", "2x16x16"):
        header, rows = fmt_table(records, mesh)
        if not rows:
            continue
        print(f"\n=== roofline @ {mesh} ===")
        print(",".join(header))
        for row in rows:
            print(",".join(row))
    errs = [r for r in records if r.get("status") != "ok"]
    if errs:
        print("\nerrors:")
        for r in errs:
            print(f"  {r['arch']} x {r['shape']} @ {r['mesh']}: "
                  f"{r.get('error', '?')[:120]}")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    if len(sys.argv) > 1 and sys.argv[1] == "beam":
        raise SystemExit(beam_main(sys.argv[2:]))
    main(sys.argv[1] if len(sys.argv) > 1 else "results/dryrun.json")
