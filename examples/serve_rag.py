"""End-to-end serving driver: retrieval-augmented generation over the
LSM-VEC online serving engine.

The paper's motivating deployment (§1): a vector database serving ANN
queries for RAG.  This driver wires the full path through `repro.serve`
(DESIGN.md §8) — requests are submitted one at a time, exactly like
independent clients would, and the engine owns batching:

  1. a small LM (the qwen3-family smoke config) embeds documents by
     mean-pooling its final hidden states;
  2. documents live in an LSM-VEC index behind a `ServeEngine`
     (micro-batched queries/inserts/deletes, snapshot-cached reads,
     threshold-driven compaction);
  3. each request: embed query -> submit to the engine -> retrieved doc
     tokens are prepended -> prefill + greedy decode continues the
     sequence.

    PYTHONPATH=src python examples/serve_rag.py
"""

import sys

sys.path.insert(0, "src")

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs
from repro.compile_cache import enable_compile_cache
from repro.core import DISK, HNSWConfig, LSMVecIndex
from repro.models import transformer as T
from repro.serve import MaintenancePolicy, ServeConfig, ServeEngine


def embed(params, cfg, tokens):
    """Mean-pooled final hidden state as the document/query embedding."""
    x = params["embed"][tokens].astype(cfg.act_dtype)
    positions = jnp.arange(x.shape[1])[None, :]
    h, _ = T._backbone(params, cfg, x, positions, remat=False)
    return np.asarray(jnp.mean(h, axis=1), np.float32)


def embed_fallback(params, cfg, tokens):
    """Mean-pooled token embeddings only — used when the transformer
    backbone cannot run (jax API drift on the model stack is a known,
    ROADMAP-tracked issue); keeps the serving path demonstrable."""
    x = params["embed"][tokens].astype(jnp.float32)
    return np.asarray(jnp.mean(x, axis=1), np.float32)


def main(n_docs=512, doc_len=24, n_requests=8, gen_len=12):
    cfg = configs.get_config("qwen3-8b", "smoke")
    model = T.Model(cfg)
    params = model.init(jax.random.key(0))

    rng = np.random.default_rng(0)
    docs = rng.integers(0, cfg.vocab_size, (n_docs, doc_len)).astype(np.int32)

    print(f"embedding {n_docs} docs with {cfg.name} ...")
    embed_fn = embed
    try:
        doc_embeds = embed_fn(params, cfg, jnp.asarray(docs))
        lm_ok = True
    except Exception as e:  # pre-existing model-stack jax API drift
        print(f"  backbone unavailable ({type(e).__name__}); "
              "falling back to token-embedding pooling")
        embed_fn = embed_fallback
        doc_embeds = embed_fn(params, cfg, jnp.asarray(docs))
        lm_ok = False
    dim = doc_embeds.shape[1]

    idx_cfg = HNSWConfig(cap=2 * n_docs, dim=dim, M=12, M_up=6,
                         num_upper=2, ef_search=32, ef_construction=32,
                         k=4, rho=0.8, use_filter=True)
    index = LSMVecIndex.build(idx_cfg, doc_embeds)
    engine = ServeEngine(index, ServeConfig(
        query_batch=n_requests, insert_batch=8, delete_batch=8,
        query_window=0.002, insert_window=0.005, delete_window=0.005,
        maintenance=MaintenancePolicy(tombstone_ratio=0.2, check_every=4)))
    print(f"index built; resident {index.memory_bytes()/1e6:.2f} MB")

    # live update: new documents arrive while serving — submitted
    # individually, coalesced by the engine into one padded batch
    new_docs = rng.integers(0, cfg.vocab_size, (8, doc_len)).astype(np.int32)
    ins = [engine.submit_insert(e)
           for e in embed_fn(params, cfg, jnp.asarray(new_docs))]
    engine.drain()
    print(f"inserted docs {[t.result() for t in ins][:4]} ... "
          f"(1 micro-batch, {engine.metrics.snapshot()['insert']['batches']}"
          " dispatched)")
    docs = np.concatenate([docs, new_docs])

    # serve a burst of requests: one submit per client, one micro-batch
    # on the device
    queries = rng.integers(0, cfg.vocab_size,
                           (n_requests, doc_len)).astype(np.int32)
    t0 = time.monotonic()
    q_embeds = embed_fn(params, cfg, jnp.asarray(queries))
    index.reset_stats()
    tickets = [engine.submit_query(q) for q in q_embeds]
    engine.drain()
    doc_ids = np.stack([t.result().ids for t in tickets])
    retrieve_cost = index.io_cost(DISK) * 1e3 / n_requests

    # prepend retrieved doc, prefill, greedy-decode continuation
    ctx = np.concatenate([docs[doc_ids[:, 0]], queries], axis=1)
    if lm_ok:
        last, state = T.prefill(params, cfg, jnp.asarray(ctx),
                                max_len=ctx.shape[1] + gen_len)
        tok = jnp.argmax(last, axis=-1).astype(jnp.int32)
        outs = [np.asarray(tok)[:, 0]]
        for _ in range(gen_len - 1):
            logits, state = T.decode_step(params, cfg, state, tok)
            tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            outs.append(np.asarray(tok)[:, 0])
        gen = np.stack(outs, axis=1)
    else:
        gen = np.zeros((n_requests, gen_len), np.int32)   # retrieval-only
    wall = time.monotonic() - t0
    m = engine.metrics.snapshot()
    print(f"served {n_requests} requests in {wall:.2f}s "
          f"({wall/n_requests*1e3:.0f} ms/req wall on 1 CPU core)")
    print(f"engine: {m['query']['batches']} query micro-batches, "
          f"mean occupancy {m['query']['mean_batch']}, "
          f"p50 {m['query']['p50_ms']:.1f} ms, "
          f"{m['snapshot_resolves']} snapshot resolves")
    print(f"modeled retrieval I/O: {retrieve_cost:.2f} ms/req "
          f"({int(index.io_stats.n_vec)} vector fetches, "
          f"{int(index.io_stats.n_filtered)} skipped by sampling)")
    for i in range(min(3, n_requests)):
        print(f"req {i}: retrieved doc {int(doc_ids[i, 0])}, "
              f"generated {gen[i][:8].tolist()} ...")


if __name__ == "__main__":
    enable_compile_cache()
    main()
