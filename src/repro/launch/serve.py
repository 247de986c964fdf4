"""gRouting serving launcher: the paper's cluster on host devices.

``python -m repro.launch.serve --scheme embed --processors 4 ...`` builds a
synthetic power-law graph, preprocesses landmark/embedding router state,
and serves the three h-hop query workloads through the event-driven cluster
(repro.core.serving), printing paper-style throughput/latency/hit-rate rows.

--device-path serves the same workload through the jitted ServingEngine
instead (set-associative row caches, hash-partitioned storage read with
multi_read, every processor vmapped on the default device), one result row
per scheme. The shard_map path across several devices is
examples/serve_graph.py."""

from __future__ import annotations

import argparse
import sys

import numpy as np


ROW_WIDTH = 32  # storage row width (continuation rows past it)
CACHE_WAYS = 4


def serve_engine(args, g, li, ge, wl, schemes) -> int:
    """Serve `wl` through ServingEngine under each scheme ("no_cache" is
    next_ready routing with the row caches off)."""
    from repro.core.router import Router, RouterConfig
    from repro.core.storage import build_storage, device_storage
    from repro.graph.csr import to_padded
    from repro.serve.engine import EngineRunConfig, ServingEngine

    store = device_storage(build_storage(to_padded(g, max_degree=ROW_WIDTH),
                                         n_shards=args.processors))
    max_degree = int(np.diff(g.indptr).max())
    for scheme in schemes:
        router = Router(args.processors,
                        RouterConfig(scheme="next_ready" if scheme == "no_cache" else scheme),
                        landmark_index=li, embedding=ge)
        cfg = EngineRunConfig(
            n_processors=args.processors, round_size=16 * args.processors,
            capacity=16, hops=args.hops, max_frontier=2048,
            cache_sets=max(1, args.cache_entries // CACHE_WAYS), cache_ways=CACHE_WAYS,
            chain_depth=-(-max_degree // ROW_WIDTH), use_cache=scheme != "no_cache",
        )
        res, _ = ServingEngine(store, router, cfg).run(wl)
        res.scheme = scheme
        print(res.row())
    return 0


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--nodes", type=int, default=20000)
    ap.add_argument("--degree", type=int, default=8)
    ap.add_argument("--processors", type=int, default=4)
    ap.add_argument("--scheme", default="all",
                    choices=["all", "no_cache", "next_ready", "hash", "landmark", "embed"])
    ap.add_argument("--workload", default="hotspot",
                    choices=["hotspot", "concentrated", "uniform"])
    ap.add_argument("--hops", type=int, default=3)
    ap.add_argument("--cache-entries", type=int, default=1 << 14)
    ap.add_argument("--landmarks", type=int, default=32)
    ap.add_argument("--device-path", action="store_true")
    args = ap.parse_args()

    from repro.graph.generators import powerlaw_graph
    from repro.core.landmarks import build_landmark_index
    from repro.core.embedding import EmbedConfig, build_graph_embedding
    from repro.core.workloads import (
        concentrated_workload, hotspot_workload, uniform_workload,
    )
    from repro.core.serving import BallCache, ServingSimulator, SimRouter, SimRouterConfig

    g = powerlaw_graph(n=args.nodes, m=args.degree, seed=0)
    print(f"[serve] graph n={g.n} e={g.e}")
    li = build_landmark_index(g, n_processors=args.processors,
                              n_landmarks=args.landmarks)
    ge = build_graph_embedding(li.dist_to_lm, li.landmarks,
                               EmbedConfig(dim=10, lm_steps=300, node_steps=100))
    print(f"[serve] preprocessing done (embed rel-err {ge.rel_error(li.dist_to_lm):.3f})")

    wl = {
        "hotspot": lambda: hotspot_workload(g, r=2, seed=1),
        "concentrated": lambda: concentrated_workload(g, seed=1),
        "uniform": lambda: uniform_workload(g, seed=1),
    }[args.workload]()

    schemes = (
        ["no_cache", "next_ready", "hash", "landmark", "embed"]
        if args.scheme == "all" else [args.scheme]
    )
    if args.device_path:
        return serve_engine(args, g, li, ge, wl, schemes)
    balls = BallCache(g)
    for scheme in schemes:
        rt = SimRouter(args.processors, SimRouterConfig(scheme=scheme),
                       landmark_index=li, embedding=ge)
        sim = ServingSimulator(
            g, args.processors, rt, cache_entries=args.cache_entries,
            h=args.hops, use_cache=(scheme != "no_cache"), ball_cache=balls,
        )
        print(sim.run(wl).row())
    return 0


if __name__ == "__main__":
    sys.exit(main())
