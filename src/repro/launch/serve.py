"""Serving CLI over the repro.serve engines.

Token LMs (dense/moe/vlm) go through :class:`repro.serve.ServeEngine`:
chunked prefill + paged KV cache + continuous batching, exactly two
traced programs for the engine's life (self-audited), optionally under
the host mesh (``--mesh-model``) with the TorchGT cluster-sparse mask
(``--sparse``).

Graph-family archs go through :class:`repro.serve.GraphServe`: the CLI
builds an SBM graph, answers node-classification and link-prediction
queries through the same reformation pipeline the training tasks use,
and reports the layout-cache behaviour.

  PYTHONPATH=src python -m repro.launch.serve --arch qwen3_0_6b \
      --requests 12 --batch 4 --chunk 16 --page 16 [--sparse] \
      [--mesh-model 2]
  PYTHONPATH=src python -m repro.launch.serve --arch graphormer_slim \
      --graph-nodes 96 --queries 8
"""

from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.configs import get_config, get_smoke_config
from repro.models import build
from repro.serve import GraphServe, ServeEngine


def serve_lm(model, args) -> int:
    params = model.init(jax.random.PRNGKey(0))
    eng = ServeEngine(model, params, batch_slots=args.batch,
                      page=args.page, max_len=args.max_len,
                      chunk=args.chunk, sparse=args.sparse,
                      mesh_model=args.mesh_model)
    rng = np.random.default_rng(0)
    cfg = model.cfg
    for rid in range(args.requests):
        plen = int(rng.integers(4, args.prompt_len + 1))
        eng.submit(rid, rng.integers(1, cfg.vocab_size // 8, plen).tolist(),
                   args.max_tokens,
                   arrival=rid * args.arrival_gap)
    stats = eng.run()
    lat = sorted(r["latency_s"] for r in eng.request_stats)
    p50 = lat[len(lat) // 2]
    p99 = lat[min(len(lat) - 1, int(len(lat) * 0.99))]
    print(f"served {stats['requests']} requests / {stats['tokens']} tokens "
          f"in {stats['seconds']:.2f}s ({stats['tok_per_s']:.1f} tok/s, "
          f"{stats['prefill_calls']} prefill + {stats['decode_calls']} "
          f"decode calls, {stats['traced_programs']} traced programs, "
          f"{args.batch} slots, page={args.page}, sparse={args.sparse})")
    print(f"latency p50={p50 * 1e3:.1f}ms p99={p99 * 1e3:.1f}ms "
          f"(free blocks at drain: {eng.allocator.n_free}/"
          f"{eng.allocator.num_blocks - 1})")
    for rid in sorted(eng.done)[:3]:
        print(f"  req {rid}: {eng.done[rid][:10]}")
    return 0


def serve_graph(model, args) -> int:
    from repro.core.graph import sbm_graph

    cfg = model.cfg
    params = model.init(jax.random.PRNGKey(0))
    g = sbm_graph(args.graph_nodes, args.graph_clusters, p_in=0.04,
                  p_out=0.002, feat_dim=cfg.feat_dim,
                  n_classes=cfg.n_classes, seed=0)
    srv = GraphServe(model, params)
    rng = np.random.default_rng(0)
    t0 = time.perf_counter()
    nodes = rng.integers(0, g.n, args.queries)
    out = srv.node(g, nodes)
    # positive (real edge) vs random pairs through the link head
    eidx = rng.integers(0, len(g.src), args.queries)
    link_pos = srv.link(g, g.src[eidx], g.dst[eidx])
    link_rnd = srv.link(g, rng.integers(0, g.n, args.queries),
                        rng.integers(0, g.n, args.queries))
    dt = time.perf_counter() - t0
    print(f"GraphServe: {g.n}-node graph, {args.queries} node + "
          f"{2 * args.queries} link queries in {dt:.2f}s "
          f"({srv.n_cached_layouts()} cached layout)")
    print(f"  node labels: {out['labels'][:8].tolist()}")
    print(f"  link score (edges):  mean {link_pos['scores'].mean():+.3f}")
    print(f"  link score (random): mean {link_rnd['scores'].mean():+.3f}")
    return 0


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3_0_6b")
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--full", dest="smoke", action="store_false")
    # token-LM engine knobs
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=12)
    ap.add_argument("--max-tokens", type=int, default=16)
    ap.add_argument("--max-len", type=int, default=256)
    ap.add_argument("--chunk", type=int, default=16)
    ap.add_argument("--page", type=int, default=16)
    ap.add_argument("--arrival-gap", type=float, default=0.0,
                    help="seconds between request arrivals (offered load)")
    ap.add_argument("--sparse", action="store_true")
    ap.add_argument("--mesh-model", type=int, default=1)
    # graph endpoint knobs
    ap.add_argument("--graph-nodes", type=int, default=96)
    ap.add_argument("--graph-clusters", type=int, default=4)
    ap.add_argument("--queries", type=int, default=8)
    args = ap.parse_args(argv)

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    model = build(cfg)
    if cfg.family == "graph":
        return serve_graph(model, args)
    if model.paged_decode is None:
        # recurrent/cross-attention decode state is not a positional KV
        # cache — fail at the CLI boundary with the servable families
        ap.error(f"--arch {args.arch} (family {cfg.family!r}) has no "
                 f"paged serving path; servable: dense/moe/vlm token LMs "
                 f"and graph archs (GraphServe)")
    return serve_lm(model, args)


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    raise SystemExit(main())
