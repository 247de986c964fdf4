"""Batched h-hop query engine (paper Algorithm 5, TPU-native).

Algorithm 5 interleaves BFS with (a) cache probes and (b) batched storage
requests for the misses. The scalar queue/set version does not map to TPU;
this engine keeps the same semantics with fixed-shape state:

  frontier      (B, F) int32   padded -1 (F = max frontier width)
  visited       the resultSet bitmap, one row per query, in the LAYOUT
                selected by `EngineConfig.visited_layout` (see below)
  cache         CacheState     shared by the whole processor (as in paper)

Per hop (== one iteration of Algorithm 5's while loop):
  1. probe cache for all frontier rows                  (lines 6-12)
  2. multi_read the misses from storage, insert to cache (lines 17-27)
  3. read the continuation rows of the frontier's hubs: a base row's
     remaining degree and first continuation id name every row of its
     chain (rows are consecutive, `graph.csr.to_padded`), so each
     processor pools its queries' chains, deduplicated and ordered by row
     id, and reads that list F ids per iteration (bounded depth per node)
  4. mark neighbors in `visited`; next frontier = newly visited nodes
     (the first F by node id keep shapes static; overflow beyond F is recorded
     in `truncated` -- with F sized to the h-hop ball this never triggers)

Step 4 -- the visited-bitmap update, the per-round hot loop -- sits behind
TWO composed seams (both python-static, resolved once per trace):

  REPRESENTATION (`EngineConfig.visited_layout`, `core.visited`):
  - "dense":  (B, n) bool -- the reference layout, one byte per node;
  - "packed": (B, ceil(n/32)) uint32 words, one BIT per node -- 8x less
    per-query state (the >100K-node scale path); result counts come from
    `lax.population_count`, set algebra is word-wise bitwise ops.

  EXECUTION (`EngineConfig.expand_backend`), per layout:
  - "scatter": XLA scatter reference (`.at[].max()` dense; packed scatters
    a transient dense delta and packs it into the word mask);
  - "pallas": ONE blocked compare-reduce kernel launch per hop
    (`kernels.frontier.frontier_expand_batched` for dense, grid (query,
    node-block, frontier-block); `frontier_expand_packed` for packed, grid
    (query, word-block, frontier-block) reducing straight into uint32
    words) -- scatter-free, the TPU path ("pallas-interpret" runs the
    identical kernel program via the interpreter on CPU);
  - "auto": `lax.cond` on frontier density per hop -- dense frontiers take
    the kernel, sparse ones the scatter (the packed layout refines the
    predicate with word popcounts, `dense_frontier_packed`). (Under the
    single-host engine's vmap over processors the cond's predicate is
    batched and XLA evaluates both branches then selects; inside shard_map
    the predicate is per-device and the cond stays a real branch.)

Every (layout, backend) pair must keep the engine<->simulator differential
oracle exactly green: touch sets, read volumes, and backlog evolution are
representation AND execution invariants (`tests/test_engine_parity.py`
parametrizes over both axes, `tests/test_expand_backends.py` sweeps the
backends against each other across frontier/bitmap shapes, and
`tests/test_visited_properties.py` is the layout property gate).

Three query types (paper §2.2) share the BFS core:
  - h-hop neighbor aggregation: |visited| - 1 (or label histogram)
  - h-step random walk with restart: separate light-weight walker (reads
    rows, never expands -- untouched by the backend choice)
  - h-hop reachability: bi-directional BFS, bitmap intersection
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import cache as cache_lib
from repro.core.cache import CacheState
from repro.core.storage import StorageTier, multi_read_ref
# The expansion backends and visited-set layouts live in core.visited; the
# names below are re-exported here because this module is their historical
# home (PR 3 pinned the backend seam's public surface here).
from repro.core.visited import (  # noqa: F401  (re-exports)
    EXPAND_BACKENDS, VISITED_LAYOUTS, get_expand_backend, get_visited_layout,
    visited_nbytes,
)


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    max_frontier: int = 2048  # F
    chain_depth: int = 64  # max rows of one node read per hop, base row
    #                         included (a cap; the chain loop reads the pooled
    #                         continuation rows F ids per iteration, so its
    #                         cost follows the rows, not this depth)
    use_cache: bool = True
    # frontier-expansion backend: how step 4 (neighbors -> visited bitmap)
    # executes. One of EXPAND_BACKENDS: "scatter" (XLA scatter, the
    # reference), "pallas" (blocked compare-reduce kernel, one launch per
    # hop), "auto" (lax.cond on frontier density per hop), or the
    # "-interpret" variants that force the Pallas interpreter (CPU tests).
    # Semantics are backend-invariant; only the execution strategy changes.
    expand_backend: str = "scatter"
    # visited-set layout: how the per-query resultSet bitmap is REPRESENTED.
    # One of VISITED_LAYOUTS: "dense" ((B, n) bool, the reference) or
    # "packed" ((B, ceil(n/32)) uint32 words, 8x smaller -- the >100K-node
    # scale path). Semantics are layout-invariant (core.visited).
    visited_layout: str = "dense"
    # when the engine runs INSIDE shard_map and multi_read contains
    # collectives (all_to_all), every participant must run the same number of
    # chain iterations: the loop condition is then psum'd over these axes
    # (the single-host engine names its processor vmap axis here, so the
    # processors share one loop).
    sync_axes: Optional[Tuple[str, ...]] = None


class HopResult(NamedTuple):
    visited: jax.Array  # per-query visited set IN THE CONFIGURED LAYOUT:
    #                     (B, n) bool (dense) or (B, ceil(n/32)) uint32 (packed)
    frontier: jax.Array  # (B, F) int32
    cache: CacheState
    truncated: jax.Array  # (B,) bool -- frontier overflow happened
    reads: jax.Array  # () int32 -- unique storage rows fetched
    touched: jax.Array  # () int32 -- rows needed (hits + misses)
    probe_misses: jax.Array  # () int32 -- missed cache probes (incl. batch dups)
    chain_iters: jax.Array  # (2,) int32 -- iterations: base read, packed loop
    chain_rows: jax.Array  # (2,) int32 -- (query, row) pairs read per stage
    chain_unique: jax.Array  # (2,) int32 -- distinct row ids read per stage


def _dedup_first(ids: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Intra-batch duplicate detection for read combining.

    ids: (M,) int32. Returns (first (M,) bool -- entry is the first
    occurrence of its value; src (M,) int32 -- index of that first
    occurrence, identity for first occurrences).
    """
    M = ids.shape[0]
    if M == 0:
        return jnp.zeros((0,), bool), jnp.zeros((0,), jnp.int32)
    order = jnp.argsort(ids, stable=True)
    s = ids[order]
    is_first_s = jnp.concatenate([jnp.ones((1,), bool), s[1:] != s[:-1]])
    head_pos_s = jax.lax.cummax(jnp.where(is_first_s, jnp.arange(M), 0))
    first_idx_s = order[head_pos_s]
    first = jnp.zeros((M,), bool).at[order].set(is_first_s)
    src = jnp.zeros((M,), jnp.int32).at[order].set(first_idx_s.astype(jnp.int32))
    return first, src


def _read_rows(
    tier_arrays,
    cache_state: CacheState,
    ids: jax.Array,
    use_cache: bool,
    multi_read: Callable,
    probes: Optional[jax.Array] = None,
) -> Tuple[jax.Array, jax.Array, jax.Array, CacheState, jax.Array, jax.Array, jax.Array]:
    """Cache-first row read with intra-batch read combining.

    ids: (M,) int32 (-1 padded). A row id requested more than once in the
    same batch is fetched from storage ONCE (RAMCloud's multi_read dedups
    its request set) and inserted into the cache once; later duplicates are
    served from the first fetch -- exactly the behaviour of a sequential
    engine, where the first access inserts and the rest hit. This also keeps
    duplicate keys from landing in multiple ways of one set (cache_insert
    requires deduped keys).

    Returns (rows, deg, cont, cache', n_probe_miss, n_reads, n_touch):
    n_probe_miss counts missed probes (consistent with the cache's own hit/
    miss counters); n_reads counts unique rows actually fetched from storage.
    `probes` (M,) int32, default one each, is how many probes each id stands
    for in n_touch and n_probe_miss: a pooled id read once for several
    queries counts as each of their probes.
    """
    valid = ids >= 0
    if probes is None:
        probes = jnp.ones(ids.shape, jnp.int32)
    n_touch = jnp.sum(jnp.where(valid, probes, 0)).astype(jnp.int32)
    if not use_cache:
        # read combining is a multi_read property, not a cache one: fetch
        # unique rows only; every probe still counts as a miss (no cache).
        first, src = _dedup_first(jnp.where(valid, ids, -1))
        uniq = valid & first
        with jax.named_scope("storage_read"):
            rows, deg, cont = multi_read(jnp.where(uniq, ids, -1))
        rows, deg, cont = rows[src], deg[src], cont[src]
        n_reads = jnp.sum(uniq).astype(jnp.int32)
        return rows, deg, cont, cache_state, n_touch, n_reads, n_touch
    with jax.named_scope("cache_lookup"):
        found, c_rows, c_deg, c_cont, cache_state = cache_lib.cache_lookup(
            cache_state, ids, valid, probes
        )
    miss = valid & ~found
    first, src = _dedup_first(jnp.where(miss, ids, -1))
    uniq = miss & first
    fetch_ids = jnp.where(uniq, ids, -1)
    with jax.named_scope("storage_read"):
        s_rows, s_deg, s_cont = multi_read(fetch_ids)
    # duplicates of a missed id read the first occurrence's fetched row
    s_rows, s_deg, s_cont = s_rows[src], s_deg[src], s_cont[src]
    with jax.named_scope("cache_insert"):
        cache_state = cache_lib.cache_insert(
            cache_state, fetch_ids, s_rows, s_deg, s_cont, valid=uniq
        )
    rows = jnp.where(found[:, None], c_rows, s_rows)
    deg = jnp.where(found, c_deg, s_deg)
    cont = jnp.where(found, c_cont, s_cont)
    n_probe_miss = jnp.sum(jnp.where(miss, probes, 0)).astype(jnp.int32)
    n_reads = jnp.sum(uniq).astype(jnp.int32)
    return rows, deg, cont, cache_state, n_probe_miss, n_reads, n_touch


class _Pool(NamedTuple):
    """A processor's continuation rows of one hop as one flat list: the
    chains its queries' frontiers name, deduplicated, ordered by row id and
    laid end to end. Chain u holds list positions [end[u] - len, end[u]),
    and list position p of chain u is row id shift[u] + p."""

    end: jax.Array  # (M,) int32 list position past each chain's last row
    shift: jax.Array  # (M,) int32 first row id minus first list position
    mult: jax.Array  # (M,) int32 queries whose frontier holds the chain
    hold: jax.Array  # (B, M) bool -- query b's frontier holds chain u
    total: jax.Array  # () int32 rows in the list
    cut: jax.Array  # () bool -- a chain is longer than the cap


def _pool_chains(deg: jax.Array, cont: jax.Array, row_width: int, cap: int) -> _Pool:
    """Pool the chains that a hop's base rows name.

    deg, cont: (B, F) remaining degree and first continuation row of each
    frontier slot's base row (0 / -1 for padding). A node of degree
    deg > row_width owns the consecutive rows cont + j for
    j < ceil(deg / row_width) - 1 (`graph.csr.to_padded`); the first `cap`
    of them are read. A node in several frontiers is one chain."""
    B, F = deg.shape
    M = B * F
    n_cont = jnp.where(cont >= 0, (deg + row_width - 1) // row_width - 1, 0)
    length = jnp.minimum(n_cont, cap).reshape(-1)
    key = jnp.where(length > 0, cont.reshape(-1), jnp.iinfo(jnp.int32).max)
    order = jnp.argsort(key)
    s = key[order]
    live = length[order] > 0
    first = live & jnp.concatenate([jnp.ones((1,), bool), s[1:] != s[:-1]])
    u = jnp.where(live, jnp.cumsum(first, dtype=jnp.int32) - 1, M)  # M: none
    head = jnp.where(first, u, M)
    start = jnp.zeros((M,), jnp.int32).at[head].set(s, mode="drop")
    chain_len = jnp.zeros((M,), jnp.int32).at[head].set(length[order], mode="drop")
    end = jnp.cumsum(chain_len, dtype=jnp.int32)
    slot_chain = jnp.zeros((M,), jnp.int32).at[order].set(u).reshape(B, F)
    hold = jnp.zeros((B, M), bool).at[jnp.arange(B)[:, None], slot_chain].set(
        True, mode="drop")
    return _Pool(end=end, shift=start - (end - chain_len),
                 mult=jnp.zeros((M,), jnp.int32).at[u].add(1, mode="drop"),
                 hold=hold, total=end[-1], cut=jnp.any(n_cont > cap))


class _Chain(NamedTuple):
    """Carry of the packed chain loop of one hop."""

    it: jax.Array  # iterations so far: list positions [0, it * F) are read
    mask: jax.Array  # visited | this hop's marks, in the layout's representation
    cache: CacheState
    reads: jax.Array
    touched: jax.Array
    probe_misses: jax.Array
    unique: jax.Array  # list rows read


def _first_set(mask: jax.Array, F: int) -> Tuple[jax.Array, jax.Array]:
    """(B, n) bool -> ((B, F) int32 positions of each row's first F set
    entries in ascending order, -1 past its last; (B,) set counts).

    The same positions as `jnp.nonzero(row, size=F, fill_value=-1)`, found
    by binary search in the row's running count: `nonzero` builds them with
    a bincount, a scatter-add of all n entries into F bins."""
    c = jnp.cumsum(mask, axis=1, dtype=jnp.int32)
    k = jnp.arange(1, F + 1, dtype=jnp.int32)
    pos = jax.vmap(lambda row: jnp.searchsorted(row, k, side="left"))(c)
    total = c[:, -1]
    return jnp.where(k[None, :] <= total[:, None], pos, -1).astype(jnp.int32), total


def expand_hop(
    tier_arrays,
    cache_state: CacheState,
    visited: jax.Array,
    frontier: jax.Array,
    cfg: EngineConfig,
    multi_read: Callable,
    n: int,
) -> HopResult:
    """One BFS hop for a batch of queries sharing one processor cache.

    Stage 0 reads every frontier slot's base row and marks them at once.
    The packed stage reads the processor's pooled continuation rows
    (`_pool_chains`) F ids per iteration and marks each query's rows among
    them at once, so every iteration of either stage is one expansion.

    `visited` is in the layout selected by `cfg.visited_layout`; the
    visited-bitmap update delegates to that layout's expansion backend
    (`cfg.expand_backend`). Both seams resolve once, python-static."""
    B, F = frontier.shape
    W = cache_state.row_width
    M = B * F
    layout = get_visited_layout(cfg.visited_layout)
    expand_fn = layout.expander(cfg.expand_backend, n)

    def _global_any(flag: jax.Array) -> jax.Array:
        """Uniform loop decision: when multi_read contains collectives, every
        shard_map participant must agree on the trip count (and a vmapped
        caller gets one unbatched loop or branch instead of a select)."""
        if cfg.sync_axes is not None:
            return jax.lax.psum(flag.astype(jnp.int32), cfg.sync_axes) > 0
        return flag

    def expand(rows: jax.Array, deg: jax.Array, mask: jax.Array) -> jax.Array:
        # mark the rows' neighbors (pluggable backend). The mask carries
        # visited | this hop's marks, not a bare delta, so the packed auto
        # backend's popcount density predicate sees the TRUE bitmap
        # occupancy (already-visited bits can't yield new marks).
        with jax.named_scope("mark"):
            return expand_fn(rows, deg, mask)

    def base_read(carry):
        cache, mask = carry
        rows, deg, cont, cache, n_probe_miss, n_reads, n_touch = _read_rows(
            tier_arrays, cache, frontier.reshape(-1), cfg.use_cache, multi_read
        )
        deg, cont = deg.reshape(B, F), cont.reshape(B, F)
        mask = expand(rows.reshape(B, F, W), deg, mask)
        return cache, mask, deg, cont, jnp.stack([n_reads, n_touch, n_probe_miss])

    def no_read(carry):
        cache, mask = carry
        return (cache, mask, jnp.zeros((B, F), jnp.int32), jnp.full((B, F), -1, jnp.int32),
                jnp.zeros((3,), jnp.int32))

    def packed_body(s: _Chain) -> _Chain:
        p = s.it * F + jnp.arange(F, dtype=jnp.int32)
        ok = p < pool.total
        c = jnp.minimum(jnp.searchsorted(pool.end, p, side="right"), M - 1)
        rows, deg, _cont, cache, n_probe_miss, n_reads, n_touch = _read_rows(
            tier_arrays, s.cache, jnp.where(ok, pool.shift[c] + p, -1), cfg.use_cache,
            multi_read, probes=jnp.where(ok, pool.mult[c], 0),
        )
        # each query marks its own rows of the read: the chains its frontier
        # holds (`pool.hold`), the rest masked out
        member = pool.hold[:, c] & ok[None, :]
        return _Chain(
            it=s.it + 1,
            mask=expand(jnp.where(member[..., None], rows[None], -1),
                        jnp.where(member, deg[None], 0), s.mask),
            cache=cache,
            reads=s.reads + n_reads,
            touched=s.touched + n_touch,
            probe_misses=s.probe_misses + n_probe_miss,
            unique=s.unique + jnp.sum(ok, dtype=jnp.int32),
        )

    z = jnp.zeros((), jnp.int32)
    with jax.named_scope("chain"):
        # stage 0: every frontier slot's base row, marked at once
        go = _global_any(jnp.any(frontier >= 0))
        cache_state, mask, deg, cont, base = jax.lax.cond(
            go, base_read, no_read, (cache_state, visited))
        ids = jnp.sort(frontier.reshape(-1))
        base_unique = jnp.sum((ids >= 0) & jnp.concatenate(
            [jnp.ones((1,), bool), ids[1:] != ids[:-1]]), dtype=jnp.int32)
        # the packed stage: the pooled continuation rows, F ids an iteration
        pool = _pool_chains(deg, cont, W, cfg.chain_depth - 1)
        s = jax.lax.while_loop(lambda s: _global_any(s.it * F < pool.total), packed_body,
                               _Chain(z, mask, cache_state, z, z, z, z))
    chain_iters = jnp.stack([go.astype(jnp.int32), s.it])
    chain_rows = jnp.stack([base[1], s.touched])
    chain_unique = jnp.stack([base_unique, s.unique])
    new_mask = s.mask

    # new_mask == visited | hop marks: the chain carry was seeded with
    # visited and every backend only ORs bits in, so it is already the
    # updated visited set -- no union pass needed in the hot loop
    # next frontier = up to F newly-visited nodes per query. Finding them
    # needs node positions, so the packed layout unpacks its DELTA here --
    # a per-hop transient XLA can fuse, not state carried across hops.
    with jax.named_scope("next_frontier"):
        newly = layout.minus(new_mask, visited)
        nxt, n_new = _first_set(layout.to_dense(newly, n), F)
    visited = new_mask
    # truncated if the frontier overflowed F, OR a chain of this processor
    # was cut off by the chain_depth cap
    truncated = (n_new > F) | pool.cut
    return HopResult(visited, nxt, s.cache, truncated, base[0] + s.reads,
                     base[1] + s.touched, base[2] + s.probe_misses,
                     chain_iters, chain_rows, chain_unique)


@dataclasses.dataclass
class QueryStats:
    """Per-batch execution statistics (feeds the cost model / Eq. 8 metrics).

    `misses` counts missed cache probes (consistent with the CacheState hit/
    miss counters, so duplicates within one batched probe each count);
    `reads` counts unique rows actually fetched from storage after intra-
    batch read combining -- the true storage read volume.

    `truncated_fwd`/`truncated_bwd` are only populated by `run_reachability`
    (per-direction detail of its bi-directional BFS: `truncated` is their
    OR); every other query type leaves them None.

    `chain_iters`/`chain_rows`/`chain_unique` are the chain loop's work, populated by `run_neighbor_aggregation`, per hop and per
    stage (0: the base rows, 1: the packed continuation rows of
    `expand_hop`): iterations, (query, row) pairs read (`chain_rows` sums to
    `touched`), distinct row ids read after the processor's deduplication
    (so `chain_rows / chain_unique` is the sharing between its queries and
    `chain_unique / (iterations x max_frontier)` the packed stage's slot
    use). Every iteration of either stage marks its read at once, so the
    expansions of the visited state are the iterations.
    """

    touched: jax.Array  # rows needed across hops (hits+misses)
    misses: jax.Array  # missed cache probes
    result_sizes: jax.Array  # (B,) |N_h(q)|
    truncated: jax.Array  # (B,) bool
    reads: jax.Array  # unique storage rows fetched
    truncated_fwd: Optional[jax.Array] = None  # (B,) bool, reachability only
    truncated_bwd: Optional[jax.Array] = None  # (B,) bool, reachability only
    chain_iters: Optional[jax.Array] = None  # (h, 2) int32
    chain_rows: Optional[jax.Array] = None  # (h, 2) int32
    chain_unique: Optional[jax.Array] = None  # (h, 2) int32


def run_neighbor_aggregation(
    tier_arrays,
    cache_state: CacheState,
    queries: jax.Array,
    h: int,
    n: int,
    cfg: EngineConfig,
    multi_read: Callable,
    touched_map: Optional[jax.Array] = None,
):
    """h-hop Neighbor Aggregation: count nodes within h hops of each query.

    queries: (B,) int32. Returns (counts (B,), cache', stats, touched_map').
    When `touched_map` (an (n,) bool bitmap) is given, the frontier's node
    rows are accumulated into it before each hop (continuation rows >= n
    are engine-internal and not tracked) -- the cache-touch-set accounting
    the engine/simulator differential oracle compares; otherwise the fourth
    value is None.
    """
    B = queries.shape[0]
    F = cfg.max_frontier
    layout = get_visited_layout(cfg.visited_layout)
    visited, frontier, valid_q = layout.init_search(queries, n, F)

    def hop(i, carry):
        (visited, frontier, cache_state, misses, reads, touched, truncated, touched_map,
         iters, rows, unique) = carry
        if touched_map is not None:
            ids = frontier.reshape(-1)
            ok = (ids >= 0) & (ids < n)
            touched_map = touched_map.at[jnp.where(ok, ids, 0)].max(ok)
        res = expand_hop(tier_arrays, cache_state, visited, frontier, cfg, multi_read, n)
        return (res.visited, res.frontier, res.cache, misses + res.probe_misses,
                reads + res.reads, touched + res.touched, truncated | res.truncated,
                touched_map, iters.at[i].set(res.chain_iters),
                rows.at[i].set(res.chain_rows), unique.at[i].set(res.chain_unique))

    z = jnp.zeros((), jnp.int32)
    per_stage = jnp.zeros((h, 2), jnp.int32)
    # one hop body in the program, looped h times (its chain loop is the
    # bulk of the program, so unrolling the hops would multiply it by h)
    (visited, _frontier, cache_state, misses, reads, touched, truncated, touched_map,
     iters, rows, unique) = jax.lax.fori_loop(
        0, h, hop, (visited, frontier, cache_state, z, z, z, jnp.zeros((B,), bool),
                    touched_map, per_stage, per_stage, per_stage))

    sizes = layout.count(visited)
    counts = sizes - valid_q.astype(jnp.int32)  # exclude query node
    stats = QueryStats(
        touched=touched, misses=misses, result_sizes=sizes,
        truncated=truncated, reads=reads, chain_iters=iters, chain_rows=rows,
        chain_unique=unique,
    )
    return counts, cache_state, stats, touched_map


def run_random_walk(
    tier_arrays,
    cache_state: CacheState,
    queries: jax.Array,
    h: int,
    n: int,
    cfg: EngineConfig,
    multi_read: Callable,
    key: jax.Array,
    restart_prob: float = 0.15,
) -> Tuple[jax.Array, CacheState, QueryStats]:
    """h-step Random Walk with Restart. Returns final node per query."""
    B = queries.shape[0]
    cur = queries
    misses = jnp.zeros((), jnp.int32)
    reads = jnp.zeros((), jnp.int32)
    touched = jnp.zeros((), jnp.int32)
    for step in range(h):
        key, k1, k2 = jax.random.split(key, 3)
        rows, deg, cont, cache_state, n_miss, n_reads, n_touch = _read_rows(
            tier_arrays, cache_state, cur, cfg.use_cache, multi_read
        )
        misses, reads, touched = misses + n_miss, reads + n_reads, touched + n_touch
        # uniform neighbor choice over the first row's own entries (paper
        # treats the value array as the neighbor set; continuation tail
        # neighbors are reached on later steps through the chain row ids
        # themselves); `deg` is the remaining degree, so the row holds
        # min(deg, W) of them
        own = jnp.minimum(deg, cache_state.row_width)
        pick = jax.random.randint(k1, (B,), 0, jnp.maximum(own, 1))
        nxt = rows[jnp.arange(B), pick]
        nxt = jnp.where(deg > 0, nxt, cur)  # dangling: stay
        restart = jax.random.uniform(k2, (B,)) < restart_prob
        cur = jnp.where(restart, queries, nxt)
        cur = jnp.where(queries >= 0, cur, -1)
    stats = QueryStats(
        touched=touched,
        misses=misses,
        result_sizes=jnp.ones((B,), jnp.int32) * (h + 1),
        truncated=jnp.zeros((B,), bool),
        reads=reads,
    )
    return cur, cache_state, stats


def run_reachability(
    tier_arrays,
    cache_state: CacheState,
    sources: jax.Array,
    targets: jax.Array,
    h: int,
    n: int,
    cfg: EngineConfig,
    multi_read: Callable,
) -> Tuple[jax.Array, CacheState, QueryStats]:
    """h-hop Reachability via bi-directional BFS (paper: forward from source,
    backward from target; the stored graph is bi-directed so one adjacency
    serves both directions). Returns reachable (B,) bool."""
    B = sources.shape[0]
    F = cfg.max_frontier
    layout = get_visited_layout(cfg.visited_layout)
    h_fwd = (h + 1) // 2
    h_bwd = h - h_fwd

    def bfs(starts, hops, cache_state):
        visited, frontier, _vq = layout.init_search(starts, n, F)
        m = jnp.zeros((), jnp.int32)
        r = jnp.zeros((), jnp.int32)
        t = jnp.zeros((), jnp.int32)
        tr = jnp.zeros((B,), bool)
        for _ in range(hops):
            res = expand_hop(tier_arrays, cache_state, visited, frontier, cfg, multi_read, n)
            visited, frontier, cache_state = res.visited, res.frontier, res.cache
            m, r, t, tr = (m + res.probe_misses, r + res.reads,
                           t + res.touched, tr | res.truncated)
        return visited, cache_state, m, r, t, tr

    vis_f, cache_state, m1, r1, t1, tr1 = bfs(sources, h_fwd, cache_state)
    vis_b, cache_state, m2, r2, t2, tr2 = bfs(targets, h_bwd, cache_state)
    reachable = layout.overlap_any(vis_f, vis_b)
    stats = QueryStats(
        touched=t1 + t2,
        misses=m1 + m2,
        result_sizes=layout.count(layout.union(vis_f, vis_b)),
        truncated=tr1 | tr2,
        reads=r1 + r2,
        truncated_fwd=tr1,
        truncated_bwd=tr2,
    )
    return reachable, cache_state, stats


def make_ref_multi_read(tier: StorageTier) -> Callable:
    """Bind the single-device storage reference for tests/simulator."""
    return functools.partial(multi_read_ref, tier)
