"""Distributed gRouting serving step -- the real pjit/shard_map execution path.

This is a THIN mesh wrapper over the unified engine step
(`repro.serve.engine.processor_round`): the per-processor serving logic --
h-hop BFS with set-associative cache + storage multi_read, stats, EMA --
lives in engine.py and is shared verbatim with the single-host
`ServingEngine`; this module only contributes the mesh concerns (shard_map
specs, the sharded all_to_all multi_read binding, psum merges).

The paper's cluster (Figure 2) on a TPU mesh:

  router state     : replicated (EMA coords per processor) -- routing math
                     is O(P*D); the EMA update (Eq. 5) is psum-merged
  query processors : every device (all mesh axes flattened); each owns a
                     set-associative LRU cache (repro.core.cache)
  storage tier     : adjacency rows sharded over "model" (the storage axis),
                     replicated across "data"/"pod" (independent read
                     replicas -- scaling the storage tier, paper §4.4);
                     multi_read = all_to_all over "model" (repro.core.storage)

One serve step:
  1. each processor runs the shared engine step over its dispatched query
     batch with its local cache, fetching misses via sharded multi_read;
  2. EMA router state is updated from the executed queries (Eq. 5) and
     psum-merged so the (replicated) router sees every processor's mean;
  3. outputs: per-query neighbor counts + global [touched, probe-misses,
     storage-reads] stats (Eq. 8).

Query->processor assignment happens OUTSIDE this step (repro.core.router /
core.dispatch, with query stealing); the step consumes already-bucketed
batches, which is how the paper's router/processor split works.
`make_admission_round` below is that outside piece with carry-over
admission: the SAME backlog-first route/dispatch/drop-oldest round the
single-host engine scans over (`repro.serve.engine.admission_dispatch`),
emitting the (n_proc, queries_per_proc) buckets this step consumes --
so oversubscribed traffic flows through the mesh path with identical
queueing semantics.

`launch/dryrun.py` lowers this function for the `grouting` cell.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import cache as cache_lib
from repro.core.dispatch import BacklogState, gather_by_dispatch, make_backlog
from repro.core.query_engine import EngineConfig
from repro.serve.engine import (
    AdmissionRound, admission_dispatch, ema_round_update,
    make_retrying_multi_read, processor_round,
)


@dataclasses.dataclass(frozen=True)
class GServeConfig:
    n_nodes: int  # graph nodes (visited bitmap width)
    n_rows: int  # storage rows (incl. continuation rows)
    row_width: int  # padded adjacency width
    n_storage_shards: int  # == model-axis size
    queries_per_proc: int  # local query batch per device
    hops: int = 2
    max_frontier: int = 256
    cache_sets: int = 512
    cache_ways: int = 4
    read_capacity: int = 4096  # per-(proc, shard) multi_read budget
    read_retry: int = 4  # bounded re-issue rounds for over-capacity requests
    chain_depth: int = 64  # max continuation-chain length (ceil(max_true_degree / row_width));
    #                        the while_loop exits as soon as no row continues, so this is a cap
    # frontier-expansion backend for the per-device engine step (see
    # repro.core.query_engine.EXPAND_BACKENDS). Inside shard_map the "auto"
    # density cond stays a REAL branch (per-device predicate), so each
    # processor picks kernel vs scatter per hop independently.
    expand_backend: str = "scatter"
    # visited-set layout for the per-device engine step (see
    # repro.core.visited.VISITED_LAYOUTS): "dense" | "packed". The packed
    # layout cuts each device's per-query BFS state 8x -- the knob that
    # lets queries_per_proc x n_nodes grow past 100K-node graphs.
    visited_layout: str = "dense"
    embed_dim: int = 10
    load_factor: float = 20.0
    alpha: float = 0.5


def _proc_axes(mesh: Mesh) -> Tuple[str, ...]:
    return tuple(a for a in ("pod", "data", "model") if a in mesh.shape)


def n_processors(mesh: Mesh) -> int:
    return int(np.prod([mesh.shape[a] for a in _proc_axes(mesh)]))


def make_distributed_serve_step(mesh: Mesh, cfg: GServeConfig):
    """Returns jit'able serve_step(inputs_dict) -> (counts, ema, cache, stats).

    inputs_dict layout == abstract_serve_inputs(mesh, cfg, rows_per_shard).
    """
    axes = _proc_axes(mesh)
    model_ax = "model"
    n_proc = n_processors(mesh)
    # sync_axes: the chain while_loop contains all_to_all over the storage
    # axis, so every participant of that collective group must run the same
    # trip count -- the loop condition is psum'd over "model".
    ecfg = EngineConfig(
        max_frontier=cfg.max_frontier, chain_depth=cfg.chain_depth,
        expand_backend=cfg.expand_backend, visited_layout=cfg.visited_layout,
        sync_axes=(model_ax,)
    )

    def local_step(queries, rows, deg, cont, owner, loc, coords, ema, *cache_leaves):
        # locals: queries (1, Q); rows (1, rps, W); cache leaves (1, ...)
        cache = cache_lib.CacheState(*[c[0] for c in cache_leaves])
        q = queries[0]
        multi_read = make_retrying_multi_read(
            rows[0], deg[0], cont[0], owner, loc,
            axis_name=model_ax, n_shards=cfg.n_storage_shards,
            capacity=cfg.read_capacity, row_width=cfg.row_width,
            retries=cfg.read_retry,
        )
        counts, new_cache, stats, _ = processor_round(
            cache, q, h=cfg.hops, n=cfg.n_nodes, ecfg=ecfg,
            multi_read=multi_read,
        )
        # processor linear index across all mesh axes
        me = jnp.zeros((), jnp.int32)
        for a in axes:
            me = me * mesh.shape[a] + jax.lax.axis_index(a)
        # Eq. 5: EMA <- alpha*EMA + (1-alpha)*mean(coords of executed queries)
        my_ema = ema_round_update(ema, me, coords, q, cfg.alpha)
        ema_delta = jnp.zeros_like(ema).at[me].set(my_ema - ema[me])
        new_ema = ema + jax.lax.psum(ema_delta, axes)
        local_stats = jnp.stack([
            stats.touched.astype(jnp.float32),
            stats.misses.astype(jnp.float32),
            stats.reads.astype(jnp.float32),
        ])
        tot_stats = jax.lax.psum(local_stats, axes)
        new_leaves = tuple(
            jnp.asarray(l)[None] for l in dataclasses.astuple(new_cache)
        )
        return (counts[None], new_ema, tot_stats) + new_leaves

    n_cache_leaves = 8  # CacheState fields
    proc_p = P(axes)
    in_specs = (
        proc_p,  # queries
        P(model_ax),  # rows: dim0 = storage shard
        P(model_ax),  # deg
        P(model_ax),  # cont
        P(),  # owner
        P(),  # loc
        P(),  # coords
        P(),  # ema
    ) + (proc_p,) * n_cache_leaves
    out_specs = (proc_p, P(), P()) + (proc_p,) * n_cache_leaves

    mapped = jax.shard_map(
        local_step, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=False
    )

    def serve_step(inputs: dict):
        cache_leaves = tuple(
            inputs["cache"][k]
            for k in ("tags", "age", "data", "deg", "cont", "clock", "hits", "misses")
        )
        out = mapped(
            inputs["queries"], inputs["rows"], inputs["deg"], inputs["cont"],
            inputs["owner"], inputs["loc"], inputs["coords"], inputs["ema"],
            *cache_leaves,
        )
        counts, ema, stats = out[0], out[1], out[2]
        new_cache = dict(
            zip(("tags", "age", "data", "deg", "cont", "clock", "hits", "misses"), out[3:])
        )
        return counts, ema, new_cache, stats

    return serve_step


def make_admission_round(router, mesh: Mesh, cfg: GServeConfig,
                         backlog_capacity: int, dispatch_rounds: int = 0):
    """Host-side admission driver for the shard_map serve step.

    Returns (admission_round, init_backlog): `admission_round(rstate,
    backlog, fresh_node, fresh_qid)` runs ONE carry-over admission round --
    backlog re-offered ahead of fresh arrivals, smart routing, bounded
    dispatch with hard stealing, drop-oldest re-queue -- and buckets the
    placed queries into the (n_proc, queries_per_proc) buffer
    `make_distributed_serve_step`'s `queries` input expects. Identical
    semantics to the single-host engine's scan body (shared
    `admission_dispatch`), so the differential oracle covers this path too.
    The router's O(n) tables enter the jitted round as arguments.
    """
    n_proc = n_processors(mesh)
    assert router.P == n_proc, (router.P, n_proc)
    n_rounds = dispatch_rounds if dispatch_rounds > 0 else n_proc

    @jax.jit
    def round_jit(tables, rstate, backlog: BacklogState, fresh_node, fresh_qid
                  ) -> Tuple[jax.Array, AdmissionRound]:
        adm = admission_dispatch(
            router, rstate, backlog, fresh_node, fresh_qid, tables=tables,
            capacity=cfg.queries_per_proc, dispatch_rounds=n_rounds,
        )
        qbuf = gather_by_dispatch(
            adm.offered_node, adm.dispatch, n_proc, cfg.queries_per_proc,
            fill_value=-1,
        )
        return qbuf, adm

    def admission_round(rstate, backlog, fresh_node, fresh_qid):
        return round_jit(router.tables, rstate, backlog, fresh_node, fresh_qid)

    return admission_round, lambda: make_backlog(backlog_capacity)


def make_processor_caches(mesh: Mesh, cfg: GServeConfig) -> dict:
    """Stacked per-processor cache states: leaves (n_proc, ...)."""
    n_proc = n_processors(mesh)
    one = cache_lib.make_cache(cfg.cache_sets, cfg.cache_ways, cfg.row_width)
    stacked = jax.tree.map(lambda x: jnp.broadcast_to(x[None], (n_proc,) + x.shape), one)
    return {
        "tags": stacked.tags, "age": stacked.age, "data": stacked.data,
        "deg": stacked.deg, "cont": stacked.cont, "clock": stacked.clock,
        "hits": stacked.hits, "misses": stacked.misses,
    }


def abstract_serve_inputs(mesh: Mesh, cfg: GServeConfig, rows_per_shard: int) -> dict:
    """ShapeDtypeStructs for the dry-run (no allocation)."""
    n_proc = n_processors(mesh)
    S, W = cfg.n_storage_shards, cfg.row_width
    sds = jax.ShapeDtypeStruct
    cache = {
        "tags": sds((n_proc, cfg.cache_sets, cfg.cache_ways), jnp.int32),
        "age": sds((n_proc, cfg.cache_sets, cfg.cache_ways), jnp.int32),
        "data": sds((n_proc, cfg.cache_sets, cfg.cache_ways, W), jnp.int32),
        "deg": sds((n_proc, cfg.cache_sets, cfg.cache_ways), jnp.int32),
        "cont": sds((n_proc, cfg.cache_sets, cfg.cache_ways), jnp.int32),
        "clock": sds((n_proc,), jnp.int32),
        "hits": sds((n_proc,), jnp.int32),
        "misses": sds((n_proc,), jnp.int32),
    }
    return {
        "queries": sds((n_proc, cfg.queries_per_proc), jnp.int32),
        "rows": sds((S, rows_per_shard, W), jnp.int32),
        "deg": sds((S, rows_per_shard), jnp.int32),
        "cont": sds((S, rows_per_shard), jnp.int32),
        "owner": sds((cfg.n_rows,), jnp.int32),
        "loc": sds((cfg.n_rows,), jnp.int32),
        "coords": sds((cfg.n_nodes, cfg.embed_dim), jnp.float32),
        "ema": sds((n_proc, cfg.embed_dim), jnp.float32),
        "cache": cache,
    }
