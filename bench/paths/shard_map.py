"""Four chips: `make_distributed_serve_step` on a (data=1, model=4) mesh, one
query processor per chip, storage sharded over `model`, so every storage
read is an all_to_all. Fed by `make_admission_round` under the
configuration's router, one round per call: the host loop of the program's
own four-chip smoke run, kept here."""

from __future__ import annotations

import numpy as np

SPANS = ("admission", "step", "scatter_back")  # the host spans `serve` records


class Served:
    def __init__(self, cfg: dict, tier, chain_depth: int, devices: list):
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as PS

        from repro.core.router import Router, RouterConfig
        from repro.core.storage import make_serving_storage
        from repro.launch.mesh import make_auto_mesh
        from repro.serve.graph_serving import (
            GServeConfig, make_admission_round, make_distributed_serve_step,
            make_processor_caches,
        )

        P = cfg["processors"]
        if len(devices) != P:
            raise ValueError(f"{P} processors need {P} chips, got {len(devices)}")
        self.round_size = P * cfg["queries_per_proc"]
        mesh = make_auto_mesh((1, P), ("data", "model"), devices)
        gcfg = GServeConfig(
            n_nodes=tier.n, n_rows=tier.n_rows, row_width=tier.row_width,
            n_storage_shards=tier.n_shards, queries_per_proc=cfg["queries_per_proc"],
            hops=cfg["hops"], max_frontier=cfg["max_frontier"],
            cache_sets=cfg["cache_sets"], cache_ways=cfg["cache_ways"],
            read_capacity=cfg["read_capacity"], read_retry=cfg["read_retry"],
            chain_depth=chain_depth, expand_backend=cfg["expand_backend"],
            visited_layout=cfg["visited_layout"], embed_dim=1,
        )
        self.procs = NamedSharding(mesh, PS(("data", "model")))
        shards = NamedSharding(mesh, PS("model"))
        repl = NamedSharding(mesh, PS())
        store = make_serving_storage(tier)
        self.inputs = {
            "rows": jax.device_put(store["rows"], shards),
            "deg": jax.device_put(store["deg"], shards),
            "cont": jax.device_put(store["cont"], shards),
            "owner": jax.device_put(store["owner"], repl),
            "loc": jax.device_put(store["loc"], repl),
            # hash routing keeps no coordinates: a 1-wide zero table feeds the
            # step's EMA update
            "coords": jax.device_put(jnp.zeros((tier.n, 1), jnp.float32), repl),
            "ema": jax.device_put(jnp.zeros((P, 1), jnp.float32), repl),
            "cache": jax.device_put(make_processor_caches(mesh, gcfg), self.procs),
        }
        del store
        self.step = jax.jit(make_distributed_serve_step(mesh, gcfg))
        router = Router(P, RouterConfig(scheme=cfg["router"]), seed=cfg["router_seed"])
        self.admission, init_backlog = make_admission_round(router, mesh, gcfg,
                                                            backlog_capacity=0)
        self.rstate, self.backlog = router.init_state(), init_backlog()
        self.queries = None  # the last round's, for `programs`
        self.first = devices[0]

    def serve(self, nodes: np.ndarray) -> dict:
        """Serve one round of at most `round_size` queries; returns when the
        answers are on the host."""
        import jax
        import jax.numpy as jnp
        from jax.profiler import TraceAnnotation

        from repro.core.dispatch import scatter_back

        B, k = self.round_size, nodes.size
        fresh = np.full(B, -1, np.int32)
        fresh[:k] = nodes
        with TraceAnnotation("admission"):
            qbuf, adm = self.admission(self.rstate, self.backlog, jnp.asarray(fresh),
                                       jnp.arange(B, dtype=jnp.int32))
            self.rstate, self.backlog = adm.rstate, adm.backlog
        with TraceAnnotation("step"):
            self.queries = jax.device_put(qbuf, self.procs)
            out_counts, ema, cache, stats = self.step(dict(self.inputs, queries=self.queries))
            self.inputs["cache"], self.inputs["ema"] = cache, ema
        with TraceAnnotation("scatter_back"):
            per_q = np.asarray(scatter_back(jax.device_put(out_counts, self.first),
                                            adm.dispatch, B))
            placed = np.asarray(adm.placed)
            off = np.asarray(adm.offered_qid)
            touched, _missed, reads = np.asarray(stats)
            per_proc = np.asarray(adm.dispatch.counts)
        counts = np.full(k, -1, np.int64)
        completed = np.zeros(k, bool)
        ok = placed & (off >= 0) & (off < k)
        counts[off[ok]] = per_q[ok]
        completed[off[ok]] = True
        return {"counts": counts, "completed": completed, "per_proc": per_proc,
                "touched": int(touched), "reads": int(reads)}

    def programs(self) -> list:
        """Optimized HLO of the serving step, the program that holds the
        window's device work."""
        return [self.step.lower(dict(self.inputs, queries=self.queries)).compile().as_text()]

    def close(self) -> None:
        self.inputs = self.step = self.admission = self.rstate = self.backlog = None
        self.queries = None
