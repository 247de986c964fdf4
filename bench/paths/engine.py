"""One chip: `ServingEngine.run`, one round per call, its state carried from
call to call, so each round has its own completion time. The processors
are vmapped on the one device."""

from __future__ import annotations

import numpy as np

SPANS = ("round",)  # the host spans `serve` records


class Served:
    def __init__(self, cfg: dict, tier, chain_depth: int, devices: list):
        from repro.core.router import Router, RouterConfig
        from repro.core.storage import device_storage
        from repro.serve.engine import EngineRunConfig, ServingEngine

        P, qpp = cfg["processors"], cfg["queries_per_proc"]
        self.round_size = P * qpp
        router = Router(P, RouterConfig(scheme=cfg["router"]), seed=cfg["router_seed"])
        self.engine = ServingEngine(device_storage(tier, devices[0]), router, EngineRunConfig(
            n_processors=P, round_size=P * qpp, capacity=qpp, hops=cfg["hops"],
            max_frontier=cfg["max_frontier"], cache_sets=cfg["cache_sets"],
            cache_ways=cfg["cache_ways"], chain_depth=chain_depth,
            expand_backend=cfg["expand_backend"], visited_layout=cfg["visited_layout"],
        ))
        self.state = None

    def serve(self, nodes: np.ndarray) -> dict:
        """Serve one round of at most `round_size` queries; returns when the
        answers are on the host."""
        from jax.profiler import TraceAnnotation

        from repro.core.workloads import Workload

        k = nodes.size
        wl = Workload(name="round", query_nodes=nodes, query_types=np.zeros(k, np.int8),
                      targets=np.full(k, -1, np.int32), hotspot_id=np.full(k, -1, np.int32))
        with TraceAnnotation("round"):
            res, self.state = self.engine.run(wl, state=self.state)
        return {"counts": res.counts, "completed": res.completed,
                "per_proc": res.per_proc_queries, "touched": res.touched, "reads": res.reads}

    def programs(self) -> list:
        """Optimized HLO of the program the window runs: the scan, from
        carried state, over one round."""
        import jax.numpy as jnp

        eng, B = self.engine, self.round_size
        xs = (jnp.zeros((1, B), jnp.int32), jnp.zeros((1, B), jnp.int32),
              jnp.zeros((1,), jnp.int32))
        return [eng.scan.lower(eng.store, eng.router.tables, *self.state, xs)
                .compile().as_text()]

    def close(self) -> None:
        self.state = self.engine = None
